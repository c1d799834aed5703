"""Falcon-H1-34B-Instruct's block (``model_type: falcon_h1``) for the
sequence lane: EVERY layer runs Mamba-2 heads and attention heads SIDE
BY SIDE on one normed input and adds both to the stream in ONE residual
add, then a dense SwiGLU. With ``h = rmsnorm(x)``::

    att = W_o softmax(q k^T / sqrt(d)) v * attention_out_multiplier
          q, k, v = W_q a, W_k a * key_multiplier, W_v a,
          a = h * attention_in_multiplier; q and k rotated whole
          (rotate-half), 20 query heads on 4 key/value heads of 128
    ssm = W_out gated_norm(y, z) * ssm_out_multiplier
          [z | x B C | dt] = W_in (h * ssm_in_multiplier) * m
          x B C = silu(conv4(x B C) + bias)      causal, depthwise
          dt = softplus(dt + dt_bias) ; A = -exp(A_log)     a head
          S = exp(dt A) S + dt x (x) B ; y = S C + D x      a head, a step
          gated_norm = rmsnorm_group(y * silu(z)) * w   (norm AFTER the gate)
    x = x + att + ssm
    x = x + W_down(silu(W_gate h2 * g_mult) * W_up h2) * d_mult, h2 = rmsnorm(x)

``m`` scales the five slices of ``W_in``'s output by ``ssm_multipliers``
(z, x, B, C, dt); B and C come in ``n_groups`` groups that ``ssm_heads /
n_groups`` heads share; tokens enter as ``item_emb[tok] *
embedding_multiplier`` and the scores leave as ``W_head rmsnorm(x) *
lm_head_multiplier``.

So a layer owns TWO memories of a session at once: key and value rows a
token (BLOCKS) and one constant-size SLOT (the float32 state ``[heads,
head dim, state]`` and the convolution's last ``kernel - 1`` inputs):
``ops/sessions.py`` holds both kinds over the SAME layers.

This file holds the device programs: the full forward
(:func:`falconh1_layer`: the tests' encoder; one segment a row) and the
two served ones, :func:`prefill_chunk` (the CHUNKED SSD form: inside a
chunk of ``spec.chunk`` positions a masked ``(C B^T * decay) x``
product, between chunks the state carried) and :func:`extend_step` (the
RECURRENT form over a query's 1-8 new token rows).
``ops/sessions.py::FalconH1Backbone`` drives them;
``ops/falconh1_reference.py`` is the plain float32 reference (the
recurrence one position at a time). The cache writes, the join of
cached and new keys, the chunk's attention over its table and the head
are ``ops/sdar.py``'s, ``ops/smallthinker.py``'s and ``ops/mla.py``'s,
a slot's reads and writes ``ops/qwen3next.py``'s.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import numpy as np

from predictionio_tpu.ops import sdar, smallthinker
from predictionio_tpu.ops.attention import PAGED_NEG
from predictionio_tpu.ops.mla import _ein, _mm, _user_rows, rms_norm
from predictionio_tpu.ops.qwen3next import (
    _hp,
    _slot_arrays,
    _write_rows,
    _write_slots,
)

@dataclasses.dataclass(frozen=True)
class HybSpec:
    """What of ``SeqRecParams`` shapes the ``falcon_h1`` programs."""

    n_layers: int
    width: int
    n_heads: int
    n_kv: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    n_groups: int
    conv: int               # the convolution's kernel
    chunk: int              # positions a chunk of the chunked form holds
    mlp_width: int
    norm_eps: float
    rope_theta: float
    compute_dtype: str
    attn_in: float
    attn_out: float
    key_mult: float
    emb_mult: float
    head_mult: float
    ssm_in: float
    ssm_mults: Tuple[float, ...]    # z, x, B, C, dt
    ssm_out: float
    mlp_mults: Tuple[float, float]  # on the gate's input, on the output

    @property
    def kv_width(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_width(self) -> int:
        """``W_in``'s outputs: z, the convolution's channels, dt."""
        return self.d_ssm + self.conv_width + self.ssm_heads

    @property
    def state_shapes(self) -> Tuple[Tuple[str, Tuple[int, ...], str, str], ...]:
        """What a session's SLOT holds in a layer: ``(name, shape,
        dtype, the component's name in memory_report())``."""
        return (("state", (self.ssm_heads, self.ssm_head_dim, self.d_state),
                 "float32", "sessionStates"),
                ("tail", (self.conv - 1, self.conv_width), self.compute_dtype,
                 "sessionConvTails"))

    @property
    def kinds(self) -> Tuple[Tuple, ...]:
        """``(name, layers, positions kept, a slot's arrays)`` of the
        layer kinds, the attention (block) kind first: BOTH name every
        layer."""
        layers = tuple(range(self.n_layers))
        return (("attn", layers, None, ()),
                ("ssm", layers, None, self.state_shapes))

    @property
    def mup(self) -> np.ndarray:
        """``m``: ``W_in``'s outputs' multipliers, a slice each."""
        gs = self.n_groups * self.d_state
        return np.repeat(np.asarray(self.ssm_mults, np.float32),
                         [self.d_ssm, self.d_ssm, gs, gs, self.ssm_heads])


def hyb_spec(params) -> HybSpec:
    """``SeqRecParams(block="falcon_h1", ...)`` -> :class:`HybSpec`."""
    need = ("n_kv_heads", "head_dim", "intermediate_size", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_chunk_size")
    zero = [k for k in need if int(getattr(params, k)) <= 0]
    if zero:
        raise ValueError(f"the falcon_h1 block needs {', '.join(zero)}")
    if (params.norm, params.positions, bool(params.tied)) != (
            "rmsnorm", "rope", False):
        raise ValueError(
            "the falcon_h1 block takes norm rmsnorm, positions rope (on "
            "its attention heads) and untied tables (tied false), as "
            "Falcon-H1 publishes it")
    H, KV, d = int(params.n_heads), int(params.n_kv_heads), \
        int(params.head_dim)
    if H % KV or d % 2:
        raise ValueError(f"{H} query heads do not share {KV} key/value "
                         "heads evenly, or head_dim is odd")
    MH, G = int(params.mamba_n_heads), int(params.mamba_n_groups)
    if MH % G:
        raise ValueError(f"{MH} Mamba heads do not share {G} groups of B "
                         "and C evenly")
    mults = tuple(float(m) for m in params.ssm_multipliers)
    mlp = tuple(float(m) for m in params.mlp_multipliers)
    if len(mults) != 5 or len(mlp) != 2:
        raise ValueError("ssm_multipliers names five slices (z, x, B, C, "
                         "dt) and mlp_multipliers two (gate, down)")
    return HybSpec(
        int(params.n_layers), int(params.rank), H, KV, d, MH,
        int(params.mamba_d_head), int(params.mamba_d_state), G,
        int(params.mamba_d_conv), int(params.mamba_chunk_size),
        int(params.intermediate_size), float(params.norm_eps),
        float(params.rope_theta), str(params.compute_dtype),
        float(params.attention_in_multiplier),
        float(params.attention_out_multiplier),
        float(params.key_multiplier), float(params.embedding_multiplier),
        float(params.lm_head_multiplier), float(params.ssm_in_multiplier),
        mults, float(params.ssm_out_multiplier), mlp)


# -- parameters ----------------------------------------------------------------

LOW_SUFFIXES = sdar.LOW_SUFFIXES + ("w_in", "w_out", "w_gate", "w_up",
                                    "w_down")
# the family's initial ranges of the decay's rate and of the step, and
# the spread of the convolution's bias (PyTorch's default for a kernel
# of 4: uniform on +-1/2, whose deviation this is)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
CONV_BIAS_STD = 0.29


def is_low(name: str) -> bool:
    """Matmul weights and the tables are held in the compute dtype when
    served; norms' weights, the convolution with its bias, ``A_log``,
    ``D`` and ``dt_bias`` stay float32."""
    return name.split("_", 1)[-1] in LOW_SUFFIXES or name in LOW_SUFFIXES


def theta_shapes(V: int, spec: HybSpec
                 ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter in drawing order, in
    ``ops/seqrec.py::_theta_shapes``'s form. ``a_log`` is the log of a
    rate drawn uniformly from ``A_RANGE``, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly from ``DT_RANGE``
    (``ops/mla.py::draw_value``); ``d_skip`` (``D``) starts from 1."""
    D, A, KW = spec.width, spec.n_heads * spec.head_dim, spec.kv_width
    F, MH = spec.mlp_width, spec.ssm_heads
    out: List[Tuple[str, Tuple[int, ...], Any]] = [
        ("item_emb", (V, D), ("div", math.sqrt(D))), ("ln_f_g", (D,), 1.0)]
    for i in range(spec.n_layers):
        p = f"l{i}_"
        for name, shape in (("wq", (D, A)), ("wk", (D, KW)), ("wv", (D, KW)),
                            ("wo", (A, D)), ("w_in", (D, spec.in_width)),
                            ("conv", (spec.conv, spec.conv_width)),
                            ("w_out", (spec.d_ssm, D)), ("w_gate", (D, F)),
                            ("w_up", (D, F)), ("w_down", (F, D))):
            out.append((p + name, shape, ("div", math.sqrt(shape[-2]))))
        out += [(p + "conv_b", (spec.conv_width,), ("mul", CONV_BIAS_STD)),
                (p + "a_log", (MH,), ("log_uniform",) + A_RANGE),
                (p + "dt_bias", (MH,),
                 ("softplus_inv_log_uniform",) + DT_RANGE),
                (p + "d_skip", (MH,), 1.0), (p + "gn_g", (spec.d_ssm,), 1.0),
                (p + "ln1_g", (D,), 1.0), (p + "ln2_g", (D,), 1.0)]
    out.append(("out_emb", (V, D), ("div", math.sqrt(D))))
    return out


def draw_serving_theta(V: int, params, skip: Tuple[str, ...] = ()):
    """The seeded parameters ``init_theta_device`` draws (same keys,
    same order), drawn ON THE DEVICE into the dtype each is served in,
    one jitted call a layer (``ops/mla.py::draw_shapes``)."""
    from predictionio_tpu.ops import mla

    spec = hyb_spec(params)
    return mla.draw_shapes(theta_shapes(V, spec), int(params.seed),
                           spec.n_layers, spec.compute_dtype, is_low, skip)


def serving_theta(theta, spec: HybSpec) -> Dict[str, Any]:
    """A (float32, host or device) ``theta`` as it is served."""
    import jax.numpy as jnp

    cd = jnp.dtype(spec.compute_dtype)
    return {k: jnp.asarray(v).astype(cd if is_low(k) else jnp.float32)
            for k, v in theta.items()}


# -- pieces --------------------------------------------------------------------

def rope(x, pos, theta: float):
    """Rotate-half rotation of the WHOLE head: ``x [T, heads, d]`` at
    ``pos [T]``. The frequencies are made on the host in float64: the
    chip's float32 power is a few ulp off, which thousands of positions
    turn into a thousandth of the rotated row (PERF.md, PR 39)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = jnp.asarray(1.0 / theta ** (np.arange(half) / half), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attn_project(theta, i: int, h, pos, spec: HybSpec):
    """The attention branch's operands from the normed input ``h: [N,
    D]`` at positions ``pos: [N]``: queries ``[N, H, d]``, keys ``[N,
    KV, d]`` (scaled by ``key_multiplier``) both rotated whole, values
    ``[N, KV, d]``."""
    p = f"l{i}_"
    N, d = h.shape[0], spec.head_dim
    a = h * spec.attn_in
    q = _mm(a, theta[p + "wq"], spec).reshape(N, spec.n_heads, d)
    k = _mm(a, theta[p + "wk"], spec).reshape(N, spec.n_kv, d) \
        * spec.key_mult
    v = _mm(a, theta[p + "wv"], spec).reshape(N, spec.n_kv, d)
    return rope(q, pos, spec.rope_theta), rope(k, pos, spec.rope_theta), v


def ssm_project(theta, i: int, h, spec: HybSpec):
    """The Mamba-2 branch's projection of ``h: [N, D]``: the gate ``z
    [N, d_ssm]``, the convolution's input ``[N, conv_width]`` (x | B |
    C, rounded to the compute dtype: what a slot's tail keeps of it)
    and the step ``dt [N, heads]`` (float32, ``softplus(. + dt_bias)``)."""
    import jax
    import jax.numpy as jnp

    p = f"l{i}_"
    S, C = spec.d_ssm, spec.conv_width
    zxbcdt = _mm(h * spec.ssm_in, theta[p + "w_in"], spec) \
        * jnp.asarray(spec.mup)
    z = zxbcdt[:, :S]
    mixed = zxbcdt[:, S:S + C].astype(jnp.dtype(spec.compute_dtype))
    dt = jax.nn.softplus(zxbcdt[:, S + C:]
                         + theta[p + "dt_bias"].astype(jnp.float32))
    return z, mixed, dt


def ssm_conv(w, b, mixed, tail, n_valid):
    """The causal depthwise convolution (with bias ``b``) of ONE
    sequence's rows ``mixed: [T, C]`` behind its ``tail: [K - 1, C]``
    (the inputs of the ``K - 1`` positions before them; zeros before
    the first event), then SiLU: ``[T, C]`` float32, and the tail after
    the first ``n_valid`` rows (rows past them are padding and shift
    nothing)."""
    import jax
    import jax.numpy as jnp

    K, T = w.shape[0], mixed.shape[0]
    xp = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=0)
    x32 = xp.astype(jnp.float32)
    y = sum(w[j].astype(jnp.float32) * x32[j:j + T] for j in range(K)) \
        + b.astype(jnp.float32)
    new_tail = jax.lax.dynamic_slice_in_dim(xp, n_valid, K - 1, axis=0)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def ssm_heads(y, spec: HybSpec):
    """The convolution's output ``[T, C]`` as ``x [T, heads, P]`` and
    ``B`` / ``C`` ``[T, heads, N]`` (a group's row repeated for the
    heads that read it)."""
    import jax.numpy as jnp

    T = y.shape[0]
    MH, P, N, G = spec.ssm_heads, spec.ssm_head_dim, spec.d_state, \
        spec.n_groups
    x = y[:, :spec.d_ssm].reshape(T, MH, P)
    Bm = y[:, spec.d_ssm:spec.d_ssm + G * N].reshape(T, G, N)
    Cm = y[:, spec.d_ssm + G * N:].reshape(T, G, N)
    rep = MH // G
    return x, jnp.repeat(Bm, rep, axis=1), jnp.repeat(Cm, rep, axis=1)


def ssd_recurrent(S, x, dt, A, Bm, Cm):
    """The selective scan one position at a time: ``S [heads, P, N]``
    float32, ``x [T, heads, P]``, ``dt [T, heads]``, ``A [heads]``
    (negative), ``Bm`` / ``Cm`` ``[T, heads, N]``. A row with ``dt = 0``
    leaves the state exactly as it was. Elementwise float32 throughout.
    Returns ``(S, y [T, heads, P])`` (without the ``D x`` skip)."""
    import jax
    import jax.numpy as jnp

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs
        S = S * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    return jax.lax.scan(step, S.astype(jnp.float32), tuple(
        a.astype(jnp.float32) for a in (x, dt, Bm, Cm)))


def ssd_chunked(S, x, dt, A, Bm, Cm, chunk: int):
    """The same scan in CHUNKS of ``chunk`` positions (``T`` a multiple
    of it). With ``c_t`` the log decay ``dt A`` summed from the chunk's
    start, inside a chunk ``y_t = exp(c_t) S_0 C_t + sum_{j <= t}
    exp(c_t - c_j) (C_t . B_j) dt_j x_j`` and the chunk hands on
    ``exp(c_Q) S_0 + sum_j exp(c_Q - c_j) dt_j x_j (x) B_j``. Algebra on
    :func:`ssd_recurrent`, float32 at full precision."""
    import jax
    import jax.numpy as jnp

    T, MH, P = x.shape
    n = T // chunk

    def split(a):       # [T, heads, ...] -> [n, heads, chunk, ...]
        return jnp.swapaxes(a.reshape((n, chunk) + a.shape[1:]), 1, 2)

    x, dt, Bm, Cm = (split(a.astype(jnp.float32)) for a in (x, dt, Bm, Cm))
    c = jnp.cumsum(dt * A[None, :, None], axis=-1)         # [n, heads, chunk]
    incl = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(incl, jnp.exp(jnp.where(
        incl, c[..., :, None] - c[..., None, :], 0.0)), 0.0)
    xdt = x * dt[..., None]
    inner = _hp("nhtj,nhjp->nhtp",
                _hp("nhts,nhjs->nhtj", Cm, Bm) * decay, xdt)

    def step(S, xs):
        xdt_i, b_i, c_dec_i, c_i, inner_i = xs
        y = _hp("hts,hps->htp", c_dec_i, S) + inner_i
        end = c_i[:, -1]
        S = S * jnp.exp(end)[:, None, None] + _hp(
            "htp,hts->hps", xdt_i * jnp.exp(end[:, None] - c_i)[..., None],
            b_i)
        return S, y

    S, y = jax.lax.scan(step, S.astype(jnp.float32),
                        (xdt, Bm, Cm * jnp.exp(c)[..., None], c, inner))
    return S, jnp.swapaxes(y, 1, 2).reshape(T, MH, P)


def gated_norm(y, z, w, spec: HybSpec):
    """The mixer's gated norm over ``y`` / ``z`` ``[T, d_ssm]``: an RMS
    norm a GROUP (``d_ssm / n_groups`` values) with one weight ``w
    [d_ssm]``; the gate ``silu(z)`` multiplies FIRST
    (``mamba_norm_before_gate`` false, as published)."""
    import jax
    import jax.numpy as jnp

    T = y.shape[0]
    g = (y * jax.nn.silu(z)).reshape(T, spec.n_groups, -1)
    return (g * jax.lax.rsqrt(jnp.mean(
        jnp.square(g), axis=-1, keepdims=True) + spec.norm_eps)
    ).reshape(T, -1) * w.astype(jnp.float32)


def _ssm_mixer(theta, i: int, h, state, tail, n_valid, spec: HybSpec, scan,
               form: str):
    """One sequence's rows ``h: [T, D]`` through layer ``i``'s Mamba-2
    branch from ``state`` and ``tail`` on, the first ``n_valid`` of
    them real: projection, convolution, the scan (``scan``, under the
    scope ``hyb/ssd/scan/<form>``), the gated norm and the output
    projection. Returns ``(ssm [T, D], state, tail)``."""
    import jax
    import jax.numpy as jnp

    p = f"l{i}_"
    T = h.shape[0]
    valid = jnp.arange(T) < n_valid
    with jax.named_scope("hyb/ssd/proj"):
        z, mixed, dt = ssm_project(theta, i, h, spec)
    with jax.named_scope("hyb/ssd/conv"):
        y, tail = ssm_conv(theta[p + "conv"], theta[p + "conv_b"], mixed,
                           tail, n_valid)
        x, Bm, Cm = ssm_heads(y, spec)
    with jax.named_scope(f"hyb/ssd/scan/{form}"):
        A = -jnp.exp(theta[p + "a_log"].astype(jnp.float32))
        state, o = scan(state, x, jnp.where(valid[:, None], dt, 0.0), A, Bm,
                        Cm)
        o = o + theta[p + "d_skip"].astype(jnp.float32)[None, :, None] * x
    with jax.named_scope("hyb/ssd/out"):
        o = gated_norm(o.reshape(T, -1), z, theta[p + "gn_g"], spec)
        out = _mm(o, theta[p + "w_out"], spec) * spec.ssm_out
    return out, state, tail


def ssm_chunk(theta, i: int, h, state, tail, n_valid, spec: HybSpec):
    """A prefill chunk's rows ``h: [C, D]`` (``C`` a multiple of
    ``spec.chunk``) through layer ``i``'s Mamba-2 branch, the CHUNKED
    form, state and convolution tail in and out."""
    return _ssm_mixer(theta, i, h, state, tail, n_valid, spec,
                      functools.partial(ssd_chunked, chunk=spec.chunk),
                      "chunked")


def ssm_step(theta, i: int, h, state, tail, n_new, spec: HybSpec):
    """A group's new token rows ``h: [B, T, D]`` through layer ``i``'s
    Mamba-2 branch, the RECURRENT form, each query from its own ``state
    [B, heads, P, N]`` and ``tail [B, K - 1, C]`` on; row ``t`` of query
    ``b`` is real iff ``t < n_new[b]``: a padded row has ``dt = 0``
    (decay 1, no input) and does not shift the tail, so a query without
    new rows hands its state and tail back bit for bit."""
    import jax

    return jax.vmap(lambda h_b, s_b, t_b, n_b: _ssm_mixer(
        theta, i, h_b, s_b, t_b, n_b, spec, ssd_recurrent, "recurrent"))(
            h, state, tail, n_new)


def mlp(theta, i: int, h2, spec: HybSpec):
    """The dense SwiGLU on ``h2: [N, D]``."""
    import jax

    p = f"l{i}_"
    g = jax.nn.silu(_mm(h2, theta[p + "w_gate"], spec) * spec.mlp_mults[0])
    return _mm(g * _mm(h2, theta[p + "w_up"], spec), theta[p + "w_down"],
               spec) * spec.mlp_mults[1]


def embed(theta, tok, spec: HybSpec):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("hyb/embed"):
        return jnp.take(theta["item_emb"], tok, axis=0).astype(
            jnp.float32) * spec.emb_mult


def head_theta(theta, spec: HybSpec):
    """``theta`` as ``ops/mla.py::score_head`` reads it: the final
    norm's weight times ``lm_head_multiplier``, so that a user's stored
    row scores against the output table as published."""
    return dict(theta, ln_f_g=theta["ln_f_g"] * spec.head_mult)


# -- the full forward pass: the tests' encoder ------------------------------------

def falconh1_layer(theta, i: int, x, seg, pos, spec: HybSpec):
    """One layer over whole rows ``x: [B, L, D]``, ONE segment a row
    from its first column on (``seg`` 0: the padding behind it, as
    ``bucket_sequences`` pads): the attention branch dense and causal,
    the Mamba-2 branch the chunked form from a zero state over the row
    padded to whole chunks, both from one normed input and added in one
    residual add; then the SwiGLU."""
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    KV, G, d = spec.n_kv, spec.group, spec.head_dim
    live = seg != 0
    h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
    with jax.named_scope("hyb/attn"):
        q, k, v = attn_project(theta, i, h.reshape(B * L, D),
                               pos.reshape(-1), spec)
        q = q.reshape(B, L, KV, G, d)
        k, v = k.reshape(B, L, KV, d), v.reshape(B, L, KV, d)
        ok = (seg[:, :, None] == seg[:, None, :]) & live[:, :, None] \
            & (pos[:, None, :] <= pos[:, :, None])
        s = _ein("btkgd,bskd->bkgts", q, k, spec) * spec.scale
        a = jax.nn.softmax(jnp.where(ok[:, None, None], s, PAGED_NEG),
                           axis=-1)
        o = _ein("bkgts,bskd->btkgd", a, v, spec).reshape(B * L, -1)
        att = _mm(o, theta[f"l{i}_wo"], spec).reshape(B, L, D) \
            * spec.attn_out
    pad = -L % spec.chunk
    state = jnp.zeros((spec.ssm_heads, spec.ssm_head_dim, spec.d_state),
                      jnp.float32)
    tail = jnp.zeros((spec.conv - 1, spec.conv_width),
                     jnp.dtype(spec.compute_dtype))
    ssm = jax.vmap(lambda h_b, n_b: ssm_chunk(
        theta, i, jnp.pad(h_b, ((0, pad), (0, 0))), state, tail, n_b,
        spec)[0][:L])(h, jnp.sum(live, axis=1))
    x = x + att + ssm
    with jax.named_scope("hyb/mlp"):
        h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
        return x + mlp(theta, i, h2.reshape(B * L, D), spec).reshape(B, L, D)


# -- the served programs, over slots and the block cache --------------------------

ATTN, SSM = 0, 1    # the kinds' places in ``HybSpec.kinds``


def prefill_chunk(theta, X, pool, ints, *, spec: HybSpec, C: int, S: int,
                  bs: int, qb: int, layout: Tuple):
    """One chunk of one session's prefill: ``C`` tokens at positions
    ``pos0 ..`` run through every layer. A layer writes their key and
    value rows, attends over the cached positions its block table
    covers (their own included), ``qb`` queries at a time, AND takes
    the session's slot (zero state and tail at ``pos0 = 0``) through
    the chunked SSD form and writes it back. ``ints`` = ``[user row
    (negative: none), pos0, valid tokens, item ids x C, the block
    kind's cache rows x C and block table, the slot kind's slot id]``.
    Returns ``X`` with the final-normed hidden state of the chunk's
    last valid token (times ``lm_head_multiplier``) in the user's row,
    the pool, and that state."""
    import jax
    import jax.numpy as jnp

    pos0, n_valid = ints[1], ints[2]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    slot = ints[layout[SSM][2]]
    wrow, _, table = smallthinker._kind_ints(ints, layout, ATTN, C)
    x = embed(theta, ints[3:3 + C], spec)
    for i in range(spec.n_layers):
        h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
        with jax.named_scope("hyb/attn"):
            q, k, v = attn_project(theta, i, h, pos, spec)
            pool = _write_rows(pool, i, k, v, wrow, bs)
            o = smallthinker.chunk_attend(
                q, pool["k"][i], pool["v"][i], table, None, pos, pos0,
                n_valid, None, spec, qb)
            att = _mm(o.reshape(C, -1), theta[f"l{i}_wo"], spec) \
                * spec.attn_out
        state, tail = _slot_arrays(pool, i, slot, pos0 == 0)
        ssm, state, tail = ssm_chunk(theta, i, h, state, tail, n_valid, spec)
        pool = _write_slots(pool, i, slot, state, tail)
        x = x + att + ssm
        with jax.named_scope("hyb/mlp"):
            x = x + mlp(theta, i, rms_norm(x, theta[f"l{i}_ln2_g"],
                                           spec.norm_eps), spec)
    h_last = rms_norm(jnp.take(x, jnp.maximum(n_valid - 1, 0), axis=0),
                      theta["ln_f_g"] * spec.head_mult, spec.norm_eps)
    return X.at[_user_rows(ints[0], X.shape[0])].set(
        h_last.astype(X.dtype), mode="drop"), pool, h_last


def extend_step(theta, X, seen_bits, pool, Y, ints, *, spec: HybSpec,
                kb: int, T: int, S: int, bs: int, n_items: int, mode: str,
                layout: Tuple, audit: bool = False):
    """One dispatch of the session lane: ``B`` queries, each appending
    up to ``T`` events to its own session and asking for its top
    ``kb``. ``ints: [B, ...]`` int32 rows ``[user row (negative: none,
    nothing is written for it), cached length, new events, item ids x
    T, the block kind's cache rows to write x T and block table, the
    slot kind's slot id]``. In EVERY layer the new rows attend over the
    session's cached rows (the paged kernel on a TPU) joined with the
    new rows themselves, then their keys and values are written, AND
    the session's slot is advanced by the recurrent form over the valid
    rows and written back (a padded query row names slot 0, which
    nobody holds); both branches read one normed input and are added
    at once. Returns the packed top-k, the new ``X``, ``seen_bits``,
    the pool and, compiled with ``audit``, what a check compares (else
    None): every item's ``scores`` ``[B, items]`` and, for each row's
    last new event, ``layers`` ``[n_layers, B, D]`` (the residual
    stream after every layer), ``att`` / ``ssm`` ``[n_layers, B, D]``
    (the two branches' outputs before the add), ``k`` / ``v``
    ``[n_layers, B, kv_width]`` (the cache rows written for it, as the
    cache holds them), ``mid`` ``[n_layers, B, D]`` (the stream behind
    the add, before the SwiGLU), ``rows`` ``[n_layers, B, T, D]`` (the
    stream after every layer for EVERY new row: what the next layer was
    given) and ``new`` ``[1, B]`` (the query's new events). Four
    float32 counters ride as int32 bits behind the packed columns:
    cache rows the layers had to read (a query's cached rows and its
    new ones, summed over layers), the valid token rows, the live
    queries, and a spare."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.mla import score_head

    B = ints.shape[0]
    D, H, KV, d = spec.width, spec.n_heads, spec.n_kv, spec.head_dim
    uid, len0, n_new = ints[:, 0], ints[:, 1], ints[:, 2]
    tok = ints[:, 3:3 + T]
    tpos = len0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    tvalid = jnp.arange(T)[None, :] < n_new[:, None]
    last = jnp.maximum(n_new - 1, 0)
    live = n_new > 0
    slot = ints[:, layout[SSM][2]]
    wrow, _, table = smallthinker._kind_ints(ints, layout, ATTN, T)
    own_ok = (tpos[:, None, :] <= tpos[:, :, None]) & tvalid[:, None, :]
    x = embed(theta, tok, spec)
    kept: Dict[str, list] = {k: [] for k in (
        "layers", "att", "ssm", "k", "v", "mid", "rows")}
    take_last = lambda a: jnp.take_along_axis(  # noqa: E731
        a, last.reshape((B, 1) + (1,) * (a.ndim - 2)), axis=1)[:, 0]
    for i in range(spec.n_layers):
        h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
        with jax.named_scope("hyb/attn"):
            q, k, v = attn_project(theta, i, h.reshape(B * T, D),
                                   tpos.reshape(-1), spec)
            k, v = k.reshape(B, T, KV, d), v.reshape(B, T, KV, d)
            pk, pv = pool["k"][i], pool["v"][i]
            o = sdar.attend(q.reshape(B, T, H, d), k.astype(pk.dtype),
                            v.astype(pv.dtype), own_ok, pk, pv, table, len0,
                            spec)
            att = _mm(o.reshape(B * T, -1), theta[f"l{i}_wo"],
                      spec).reshape(B, T, D) * spec.attn_out
            pool = _write_rows(pool, i, k, v, wrow.reshape(-1), bs)
        state, tail = _slot_arrays(pool, i, slot, len0 == 0)
        ssm, state, tail = ssm_step(theta, i, h, state, tail, n_new, spec)
        pool = _write_slots(pool, i, slot, state, tail)
        x = x + att + ssm
        if audit:
            with jax.named_scope("hyb/audit"):
                held = lambda a, p=pk: take_last(  # noqa: E731
                    a.reshape(B, T, -1)).astype(p.dtype).astype(jnp.float32)
                kept["k"].append(held(k))
                kept["v"].append(held(v))
                kept["att"].append(take_last(att))
                kept["ssm"].append(take_last(ssm))
                kept["mid"].append(take_last(x))
        with jax.named_scope("hyb/mlp"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            x = x + mlp(theta, i, h2.reshape(B * T, D), spec).reshape(B, T, D)
        if audit:
            with jax.named_scope("hyb/audit"):
                kept["rows"].append(x)
                kept["layers"].append(take_last(x))
    with jax.named_scope("hyb/head"):
        read = jnp.sum(jnp.where(live, len0 + n_new, 0)) \
            * jnp.float32(spec.n_layers)
        counts = jnp.stack([read] + [jnp.float32(0)] * 3)
        packed, X, seen_bits, scores = score_head(
            head_theta(theta, spec), X, seen_bits, Y,
            jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], uid,
            n_new, tok, tvalid, counts, eps=spec.norm_eps, kb=kb,
            n_items=n_items, mode=mode, mask_seen=True)
    if not audit:
        return packed, X, seen_bits, pool, None
    return packed, X, seen_bits, pool, dict(
        {k: jnp.stack(v) for k, v in kept.items()}, scores=scores,
        new=n_new[None])
