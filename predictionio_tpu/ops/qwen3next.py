"""Qwen3-Next-80B-A3B's block (``model_type: qwen3_next``) for the
sequence lane: layers of TWO KINDS in a published period
(``full_attention_interval`` 4: layer ``i`` is GATED SOFTMAX ATTENTION
where ``(i + 1) % 4 == 0`` and a GATED DELTANET layer otherwise), every
layer followed by 512 softmax-routed experts of which 10 a token
(renormalised) beside ONE shared expert scaled by a sigmoid of its own
``width -> 1`` gate. Norms are zero-centred (``x / rms(x) * (1 + w)``).

A Gated DeltaNet layer keeps no row a token. It keeps, a SESSION, one
float32 state ``[value heads, key dim, value dim]`` that every token
decays, corrects by the delta rule and reads, and the last ``kernel -
1`` inputs of a causal depthwise convolution over its query, key and
value channels::

    S   = exp(g_t) S
    d_t = beta_t (v_t - S^T k_t)
    S   = S + k_t d_t^T
    o_t = S^T q_t

so the session lane holds one SLOT a session for these layers beside
the BLOCKS of key and value rows its attention layers hold
(``ops/sessions.py``: a slot kind and a block kind under one manager).
The attention layer: 16 heads of 256 on 2 key/value heads, QK norms,
the first ``partial_rotary_factor`` of a head rotated (half-split
pairs), a sigmoid gate on the attention's output from the query
projection's second half.

This file holds the device programs: the full forward
(:func:`qwen3next_layer`: the tests' encoder; one segment a row) and
the two served ones, :func:`prefill_chunk` (the CHUNKED form of the
rule: inside a chunk of ``GDN_CHUNK`` positions the updates are a
unit-lower-triangular solve, between chunks the state is carried) and
:func:`extend_step` (the RECURRENT form over a query's 1-8 new token
rows). ``ops/sessions.py::Qwen3NextBackbone`` drives them;
``ops/qwen3next_reference.py`` is the plain float32 reference (the
recurrence one position at a time). The rotation, the cache writes, the
join of cached and new keys, the routed experts' call, the chunk's
attention over its table and the head are ``ops/sdar.py``'s,
``ops/smallthinker.py``'s and ``ops/mla.py``'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.ops import sdar, smallthinker
from predictionio_tpu.ops.attention import PAGED_NEG
from predictionio_tpu.ops.mla import _ein, _mm, _user_rows, rms_norm

GDN_CHUNK = 64          # positions a chunk of the chunked form holds
L2_EPS = 1e-6           # under the root of a head's L2 norm
KIND_NAMES = ("linear", "full")     # by the pattern's value of a layer


@dataclasses.dataclass(frozen=True)
class LinSpec:
    """What of ``SeqRecParams`` shapes the ``qwen3_next`` programs."""

    n_layers: int
    width: int
    n_heads: int
    n_kv: int
    head_dim: int
    rot_dim: int            # the leading values of a head that rotate
    k_heads: int            # DeltaNet: key (and query) heads
    v_heads: int            # DeltaNet: value heads (the state's)
    k_dim: int
    v_dim: int
    conv: int               # the convolution's kernel
    interval: int           # full_attention_interval
    expert_width: int
    n_experts: int          # the router's outputs
    held: int               # experts this chip holds
    first: int              # the first of them
    per_token: int
    shared_width: int
    norm_eps: float
    rope_theta: float
    compute_dtype: str
    renorm: bool = True

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
    def conv_width(self) -> int:
        """Channels the convolution runs over: q, k and v."""
        return 2 * self.k_heads * self.k_dim + self.v_heads * self.v_dim

    @property
    def pattern(self) -> Tuple[int, ...]:
        """A layer: 1 gated attention, 0 Gated DeltaNet."""
        return tuple(int((i + 1) % self.interval == 0)
                     for i in range(self.n_layers))

    @property
    def state_shapes(self) -> Tuple[Tuple[str, Tuple[int, ...], str, str], ...]:
        """What a session's SLOT holds in a DeltaNet layer: ``(name,
        shape, dtype, the component's name in memory_report())``."""
        return (("state", (self.v_heads, self.k_dim, self.v_dim), "float32",
                 "sessionStates"),
                ("tail", (self.conv - 1, self.conv_width), self.compute_dtype,
                 "sessionConvTails"))

    @property
    def kinds(self) -> Tuple[Tuple, ...]:
        """``(name, layers, positions kept, a slot's arrays)`` of the
        layer kinds, the attention (block) kind first."""
        out = []
        for g, state in ((1, ()), (0, self.state_shapes)):
            layers = tuple(i for i, p in enumerate(self.pattern) if p == g)
            if layers:
                out.append((KIND_NAMES[g], layers, None, state))
        return tuple(out)

    def kind_of(self, i: int) -> int:
        """Layer ``i``'s index into :attr:`kinds`."""
        return [k[0] for k in self.kinds].index(KIND_NAMES[self.pattern[i]])

    def index_in_kind(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind: where the
        pool's arrays of that kind hold it."""
        return sum(1 for j in range(i) if self.pattern[j] == self.pattern[i])


def lin_spec(params) -> LinSpec:
    """``SeqRecParams(block="qwen3_next", ...)`` -> :class:`LinSpec`."""
    need = ("n_kv_heads", "head_dim", "n_experts", "expert_width",
            "experts_per_token", "linear_key_heads", "linear_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel", "full_attention_interval",
            "shared_expert_width")
    zero = [k for k in need if int(getattr(params, k)) <= 0]
    if zero:
        raise ValueError(f"the qwen3_next block needs {', '.join(zero)}")
    if (params.norm, params.positions, bool(params.tied),
            bool(params.norm_topk_prob)) != ("rmsnorm", "rope", False, True):
        raise ValueError(
            "the qwen3_next block takes norm rmsnorm, positions rope (on "
            "its attention layers), untied tables (tied false) and "
            "norm_topk_prob true, as Qwen3-Next publishes it")
    H, KV, d = int(params.n_heads), int(params.n_kv_heads), \
        int(params.head_dim)
    rot = int(round(d * float(params.partial_rotary_factor)))
    if H % KV or rot % 2 or not 0 < rot <= d:
        raise ValueError(f"{H} query heads do not share {KV} key/value "
                         f"heads evenly, or {rot} of {d} values do not "
                         "rotate in pairs")
    KH, VH = int(params.linear_key_heads), int(params.linear_value_heads)
    if VH % KH:
        raise ValueError(f"{VH} value heads do not share {KH} key heads "
                         "evenly")
    if not 2 <= int(params.full_attention_interval) <= int(params.n_layers):
        raise ValueError(
            "the qwen3_next block needs a whole period of its layers: "
            f"full_attention_interval {params.full_attention_interval} of "
            f"{params.n_layers} layers")
    E = int(params.n_experts)
    held = int(params.experts_held) or E
    first = int(params.expert_share) * held
    if int(params.experts_per_token) > E or first + held > E:
        raise ValueError("experts_per_token over n_experts, or the held "
                         "share lies past the router's outputs")
    return LinSpec(
        int(params.n_layers), int(params.rank), H, KV, d, rot, KH, VH,
        int(params.linear_key_head_dim), int(params.linear_value_head_dim),
        int(params.linear_conv_kernel), int(params.full_attention_interval),
        int(params.expert_width), E, held, first,
        int(params.experts_per_token), int(params.shared_expert_width),
        float(params.norm_eps), float(params.rope_theta),
        str(params.compute_dtype))


# -- parameters ----------------------------------------------------------------

LOW_SUFFIXES = sdar.LOW_SUFFIXES + ("w_qkvz", "w_ba", "w_out", "ws_gate",
                                    "ws_up", "ws_down")
# the family's initial ranges of the decay's rate and of the step
A_RANGE = (1e-6, 16.0)
DT_RANGE = (1e-3, 1e-1)


def is_low(name: str) -> bool:
    """Matmul weights and the tables are held in the compute dtype when
    served; norms' weights, the routers, the shared expert's gate, the
    convolution and the decay's parameters stay float32."""
    return name.split("_", 1)[-1] in LOW_SUFFIXES or name in LOW_SUFFIXES


def theta_shapes(V: int, spec: LinSpec
                 ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter in drawing order, in
    ``ops/seqrec.py::_theta_shapes``'s form. The zero-centred norms
    start from 0, the gated norm's plain weight from 1; ``a_log`` is the
    log of a rate drawn uniformly from ``A_RANGE``, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly from ``DT_RANGE``
    (``ops/mla.py::draw_value``)."""
    D, A, KW = spec.width, spec.n_heads * spec.head_dim, spec.kv_width
    E, F, FS = spec.held, spec.expert_width, spec.shared_width
    VW = spec.v_heads * spec.v_dim
    out: List[Tuple[str, Tuple[int, ...], Any]] = [
        ("item_emb", (V, D), ("div", math.sqrt(D))), ("ln_f_g", (D,), 0.0)]
    for i, full in enumerate(spec.pattern):
        p = f"l{i}_"
        if full:
            mats = (("wq", (D, 2 * A)), ("wk", (D, KW)), ("wv", (D, KW)),
                    ("wo", (A, D)))
        else:
            mats = (("w_qkvz", (D, spec.conv_width + VW)),
                    ("w_ba", (D, 2 * spec.v_heads)),
                    ("conv", (spec.conv, spec.conv_width)),
                    ("w_out", (VW, D)))
        mats += (("router", (D, spec.n_experts)), ("we_gate", (E, D, F)),
                 ("we_up", (E, D, F)), ("we_down", (E, F, D)),
                 ("ws_gate", (D, FS)), ("ws_up", (D, FS)),
                 ("ws_down", (FS, D)), ("sg", (D, 1)))
        for name, shape in mats:
            out.append((p + name, shape, ("div", math.sqrt(shape[-2]))))
        if full:
            out += [(p + "qn_g", (spec.head_dim,), 0.0),
                    (p + "kn_g", (spec.head_dim,), 0.0)]
        else:
            out += [(p + "a_log", (spec.v_heads,), ("log_uniform",) + A_RANGE),
                    (p + "dt_bias", (spec.v_heads,),
                     ("softplus_inv_log_uniform",) + DT_RANGE),
                    (p + "gn_g", (spec.v_dim,), 1.0)]
        out += [(p + "ln1_g", (D,), 0.0), (p + "ln2_g", (D,), 0.0)]
    out.append(("out_emb", (V, D), ("div", math.sqrt(D))))
    return out


def draw_serving_theta(V: int, params, skip: Tuple[str, ...] = ()):
    """The seeded parameters ``init_theta_device`` draws (same keys,
    same order), drawn ON THE DEVICE into the dtype each is served in,
    one jitted call a layer (``ops/mla.py::draw_shapes``)."""
    from predictionio_tpu.ops import mla

    spec = lin_spec(params)
    return mla.draw_shapes(theta_shapes(V, spec), int(params.seed),
                           spec.n_layers, spec.compute_dtype, is_low, skip)


def serving_theta(theta, spec: LinSpec) -> Dict[str, Any]:
    """A (float32, host or device) ``theta`` as it is served."""
    import jax.numpy as jnp

    cd = jnp.dtype(spec.compute_dtype)
    return {k: jnp.asarray(v).astype(cd if is_low(k) else jnp.float32)
            for k, v in theta.items()}


# -- pieces --------------------------------------------------------------------

def rms0(x, w, eps: float):
    """The zero-centred RMS norm: ``x / rms(x) * (1 + w)``."""
    return rms_norm(x, 1.0 + w, eps)


def _hp(sub: str, a, b):
    """A float32 contraction the rule takes at full precision (the
    state is float32: a product of rounded operands is a rounded
    state)."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum(sub, a, b, precision=jax.lax.Precision.HIGHEST)


def attn_project(theta, i: int, h, pos, spec: LinSpec):
    """An attention layer's operands from the normed input ``h: [N,
    D]`` at positions ``pos: [N]``: queries ``[N, H, d]`` and keys ``[N,
    KV, d]`` (normed a head, zero-centred weights, their first
    ``rot_dim`` values rotated), values ``[N, KV, d]`` and the output's
    gate ``[N, H x d]`` (the query projection's second half, a head)."""
    import jax.numpy as jnp

    p = f"l{i}_"
    N, H, KV, d = h.shape[0], spec.n_heads, spec.n_kv, spec.head_dim
    qg = _mm(h, theta[p + "wq"], spec).reshape(N, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm(h, theta[p + "wk"], spec).reshape(N, KV, d)
    v = _mm(h, theta[p + "wv"], spec).reshape(N, KV, d)
    q = rms0(q, theta[p + "qn_g"], spec.norm_eps)
    k = rms0(k, theta[p + "kn_g"], spec.norm_eps)

    def rotate(x):
        r = spec.rot_dim
        return jnp.concatenate(
            [sdar.rope_half(x[..., :r], pos, spec.rope_theta),
             x[..., r:].astype(jnp.float32)], axis=-1)

    return rotate(q), rotate(k), v, gate.reshape(N, H * d)


def gdn_project(theta, i: int, h, spec: LinSpec):
    """A DeltaNet layer's projections of ``h: [N, D]``: the
    convolution's input ``[N, conv_width]`` (q | k | v, rounded to the
    compute dtype: what a slot's tail keeps of it), the output's gate
    ``z [N, VH, dv]``, ``beta [N, VH]`` and the log decay ``g [N, VH]``
    (float32)."""
    import jax
    import jax.numpy as jnp

    p = f"l{i}_"
    N, VH, C = h.shape[0], spec.v_heads, spec.conv_width
    qkvz = _mm(h, theta[p + "w_qkvz"], spec)
    ba = _mm(h, theta[p + "w_ba"], spec)
    mixed = qkvz[:, :C].astype(jnp.dtype(spec.compute_dtype))
    z = qkvz[:, C:].reshape(N, VH, spec.v_dim)
    beta = jax.nn.sigmoid(ba[:, :VH])
    g = -jnp.exp(theta[p + "a_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, VH:] + theta[p + "dt_bias"].astype(jnp.float32))
    return mixed, z, beta, g


def gdn_conv(w, mixed, tail, n_valid):
    """The causal depthwise convolution of ONE sequence's rows ``mixed:
    [T, C]`` behind its ``tail: [K - 1, C]`` (the inputs of the ``K -
    1`` positions before them; zeros before the first event), then
    SiLU: ``[T, C]`` float32, and the tail after the first ``n_valid``
    rows (rows past them are padding and shift nothing)."""
    import jax
    import jax.numpy as jnp

    K, T = w.shape[0], mixed.shape[0]
    xp = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=0)
    x32 = xp.astype(jnp.float32)
    y = sum(w[j].astype(jnp.float32) * x32[j:j + T] for j in range(K))
    new_tail = jax.lax.dynamic_slice_in_dim(xp, n_valid, K - 1, axis=0)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def gdn_heads(y, spec: LinSpec):
    """The convolution's output ``[T, C]`` as heads: ``q`` and ``k``
    ``[T, VH, dk]`` (L2-normed a head, ``q`` scaled by ``1 / sqrt(dk)``,
    a key head repeated for the value heads that read it) and ``v [T,
    VH, dv]``."""
    import jax
    import jax.numpy as jnp

    T = y.shape[0]
    KH, VH, dk, dv = spec.k_heads, spec.v_heads, spec.k_dim, spec.v_dim

    def l2(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q = l2(y[:, :KH * dk].reshape(T, KH, dk)) * (dk ** -0.5)
    k = l2(y[:, KH * dk:2 * KH * dk].reshape(T, KH, dk))
    v = y[:, 2 * KH * dk:].reshape(T, VH, dv)
    rep = VH // KH
    return jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1), v


def delta_recurrent(S, q, k, v, g, beta):
    """The gated delta rule one position at a time: ``S [VH, dk, dv]``
    float32, ``q`` / ``k`` ``[T, VH, dk]``, ``v [T, VH, dv]``, ``g`` /
    ``beta`` ``[T, VH]``. A row with ``g = 0`` and ``beta = 0`` leaves
    the state exactly as it was. Elementwise float32 throughout.
    Returns ``(S, o [T, VH, dv])``."""
    import jax
    import jax.numpy as jnp

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    return jax.lax.scan(step, S, (q, k, v, g, beta))


def delta_chunked(S, q, k, v, g, beta, chunk: Optional[int] = None):
    """The same rule in CHUNKS of ``chunk`` positions (``T`` a multiple
    of it). With ``c_t`` the log decay summed from the chunk's start,
    the corrections ``d`` of a chunk solve the unit-lower-triangular
    system ``(I + A) d = beta (v - exp(c) k S_0)``, ``A[t, j] = beta_t
    exp(c_t - c_j) k_t . k_j`` for ``j < t``; then ``o_t = exp(c_t)
    S_0^T q_t + sum_{j <= t} exp(c_t - c_j) (k_j . q_t) d_j`` and the
    chunk hands on ``exp(c_C) S_0 + sum_j exp(c_C - c_j) k_j d_j^T``.
    Algebra on :func:`delta_recurrent`, float32 at full precision."""
    import jax
    import jax.numpy as jnp

    T, VH, dv = v.shape
    chunk = chunk or GDN_CHUNK
    n = T // chunk

    def split(a):       # [T, VH, ...] -> [n, VH, chunk, ...]
        return jnp.swapaxes(a.reshape((n, chunk) + a.shape[1:]), 1, 2)

    q, k, v, g, beta = (split(a.astype(jnp.float32))
                        for a in (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-1)                              # [n, VH, chunk]
    incl = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.where(incl, jnp.exp(jnp.where(
        incl, c[..., :, None] - c[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    A = jnp.where(strict, _hp("nhtd,nhjd->nhtj", kb, k) * decay, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(c)[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=jnp.float32), rhs, lower=True,
        unit_diagonal=True)
    value, k_dec = solved[..., :dv], solved[..., dv:]
    qk = _hp("nhtd,nhjd->nhtj", q, k) * decay

    def step(S, xs):
        q_i, k_i, value_i, k_dec_i, qk_i, c_i = xs
        d = value_i - _hp("htk,hkv->htv", k_dec_i, S)
        o = _hp("htk,hkv->htv", q_i * jnp.exp(c_i)[..., None], S) \
            + _hp("htj,hjv->htv", qk_i, d)
        end = c_i[:, -1]
        S = S * jnp.exp(end)[:, None, None] + _hp(
            "htk,htv->hkv", k_i * jnp.exp(end[:, None] - c_i)[..., None], d)
        return S, o

    S, o = jax.lax.scan(step, S.astype(jnp.float32),
                        (q, k, value, k_dec, qk, c))
    return S, jnp.swapaxes(o, 1, 2).reshape(T, VH, dv)


def _gdn_mixer(theta, i: int, h, state, tail, n_valid, spec: LinSpec, rule):
    """One sequence's rows ``h: [T, D]`` through a DeltaNet layer's
    mixer from ``state`` and ``tail`` on, the first ``n_valid`` of them
    real: projections, convolution, the rule (``rule``), the gated norm
    and the output projection. Returns ``(y [T, D], state, tail)``."""
    import jax
    import jax.numpy as jnp

    p = f"l{i}_"
    T = h.shape[0]
    valid = jnp.arange(T) < n_valid
    with jax.named_scope("lin/gdn/proj"):
        mixed, z, beta, g = gdn_project(theta, i, h, spec)
    with jax.named_scope("lin/gdn/conv"):
        y, tail = gdn_conv(theta[p + "conv"], mixed, tail, n_valid)
        q, k, v = gdn_heads(y, spec)
    with jax.named_scope("lin/gdn/rule"):
        keep = valid[:, None]
        state, o = rule(state, q, k, v, jnp.where(keep, g, 0.0),
                        jnp.where(keep, beta, 0.0))
    with jax.named_scope("lin/gdn/out"):
        o = rms_norm(o, theta[p + "gn_g"], spec.norm_eps) * jax.nn.silu(z)
        out = _mm(o.reshape(T, -1), theta[p + "w_out"], spec)
    return out, state, tail


def gdn_chunk(theta, i: int, h, state, tail, n_valid, spec: LinSpec):
    """A prefill chunk's rows ``h: [C, D]`` (``C`` a multiple of
    ``GDN_CHUNK``) through layer ``i``'s DeltaNet mixer, the CHUNKED
    form, state and convolution tail in and out."""
    return _gdn_mixer(theta, i, h, state, tail, n_valid, spec,
                      delta_chunked)


def gdn_step(theta, i: int, h, state, tail, n_new, spec: LinSpec):
    """A group's new token rows ``h: [B, T, D]`` through layer ``i``'s
    DeltaNet mixer, the RECURRENT form, each query from its own
    ``state [B, VH, dk, dv]`` and ``tail [B, K - 1, C]`` on; row ``t``
    of query ``b`` is real iff ``t < n_new[b]``: a padded row has ``g =
    0`` and ``beta = 0`` and does not shift the tail, so a query without
    new rows hands its state and tail back bit for bit."""
    import jax

    return jax.vmap(lambda h_b, s_b, t_b, n_b: _gdn_mixer(
        theta, i, h_b, s_b, t_b, n_b, spec, delta_recurrent))(
            h, state, tail, n_new)


def experts(theta, i: int, h2, valid, spec: LinSpec):
    """The expert layer on ``h2: [N, D]`` (``post_attention_layernorm
    (x)``): the held share of the routed experts (``sdar.moe_layer``
    from ``spec.first`` on: a pick of an expert held elsewhere adds
    nothing here) and the shared expert scaled by the sigmoid of its
    gate. Returns ``(routed [N, D], shared [N, D], picks [N, k], gates
    [N, k], held experts a valid row picked, (valid row, pick) pairs
    found here, the shared expert's gate [N])``."""
    import jax
    import jax.numpy as jnp

    p = f"l{i}_"
    y, e, w, touched = sdar.moe_layer(theta, i, h2, valid, spec,
                                      first=spec.first)
    here = (e >= spec.first) & (e < spec.first + spec.held) & valid[:, None]
    sg = jax.nn.sigmoid(jnp.dot(
        h2.astype(jnp.float32), theta[p + "sg"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))[:, 0]
    hs = jax.nn.silu(_mm(h2, theta[p + "ws_gate"], spec)) \
        * _mm(h2, theta[p + "ws_up"], spec)
    shared = sg[:, None] * _mm(hs, theta[p + "ws_down"], spec)
    return y, shared, e, w, touched, jnp.sum(here), sg


def _scope(spec: LinSpec, i: int) -> str:
    return "lin/attn" if spec.pattern[i] else "lin/gdn"


# -- the full forward pass: the tests' encoder ------------------------------------

def qwen3next_layer(theta, i: int, x, seg, pos, spec: LinSpec):
    """One layer over whole rows ``x: [B, L, D]``, ONE segment a row
    from its first column on (``seg`` 0: the padding behind it, as
    ``bucket_sequences`` pads): an attention layer is dense and causal;
    a DeltaNet layer runs the chunked form from a zero state over the
    row padded to whole chunks."""
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    KV, G, d = spec.n_kv, spec.group, spec.head_dim
    live = seg != 0
    with jax.named_scope(_scope(spec, i)):
        h = rms0(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
        if spec.pattern[i]:
            q, k, v, gate = attn_project(theta, i, h.reshape(B * L, D),
                                         pos.reshape(-1), spec)
            q = q.reshape(B, L, KV, G, d)
            k, v = k.reshape(B, L, KV, d), v.reshape(B, L, KV, d)
            ok = (seg[:, :, None] == seg[:, None, :]) & live[:, :, None] \
                & (pos[:, None, :] <= pos[:, :, None])
            s = _ein("btkgd,bskd->bkgts", q, k, spec) * spec.scale
            a = jax.nn.softmax(jnp.where(ok[:, None, None], s, PAGED_NEG),
                               axis=-1)
            o = _ein("bkgts,bskd->btkgd", a, v, spec).reshape(B * L, -1)
            y = _mm(o * jax.nn.sigmoid(gate), theta[f"l{i}_wo"],
                    spec).reshape(B, L, D)
        else:
            pad = -L % GDN_CHUNK
            state = jnp.zeros((spec.v_heads, spec.k_dim, spec.v_dim),
                              jnp.float32)
            tail = jnp.zeros((spec.conv - 1, spec.conv_width),
                             jnp.dtype(spec.compute_dtype))
            y = jax.vmap(lambda h_b, n_b: gdn_chunk(
                theta, i, jnp.pad(h_b, ((0, pad), (0, 0))), state, tail,
                n_b, spec)[0][:L])(h, jnp.sum(live, axis=1))
        x = x + y
    with jax.named_scope("lin/moe"):
        h2 = rms0(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
        routed, shared, *_ = experts(theta, i, h2.reshape(B * L, D),
                                     live.reshape(-1), spec)
    return x + (routed + shared).reshape(B, L, D)


# -- the served programs, over slots and the block cache --------------------------

def _slot(ints, layout, spec: LinSpec):
    """The DeltaNet kind's part of a row (or rows) of ``ints``: the
    session's slot id (``sessions.kind_layout``: a slot kind's "table"
    is that one id)."""
    return ints[..., layout[spec.kind_of(spec.pattern.index(0))][2]]


def _slot_arrays(pool, j: int, slot, fresh):
    """Layer ``j`` (of the DeltaNet kind)'s state and tail of the
    sessions in ``slot``; zeros for a session without events yet
    (``fresh``): a slot is handed out as it was left."""
    import jax.numpy as jnp

    out = []
    for name in ("state", "tail"):
        a = jnp.take(pool[name][j], slot, axis=0)
        out.append(jnp.where(
            jnp.reshape(fresh, jnp.shape(fresh) + (1,) * (
                a.ndim - jnp.ndim(fresh))), jnp.zeros_like(a), a))
    return out


def _write_rows(pool, j: int, k, v, rows, bs: int):
    """Attention layer ``j`` (of its kind)'s keys and values into its
    pool rows (``sdar._write_layer``, which knows ``k`` and ``v``
    alone)."""
    return dict(pool, **sdar._write_layer(
        {"k": pool["k"], "v": pool["v"]}, j, k, v, rows, bs))


def _write_slots(pool, j: int, slot, state, tail):
    out = dict(pool)
    for name, new in (("state", state), ("tail", tail)):
        a = pool[name][j].at[slot].set(new.astype(pool[name][j].dtype))
        out[name] = pool[name][:j] + (a,) + pool[name][j + 1:]
    return out


def prefill_chunk(theta, X, pool, ints, *, spec: LinSpec, C: int, S: int,
                  bs: int, qb: int, layout: Tuple):
    """One chunk of one session's prefill: ``C`` tokens at positions
    ``pos0 ..`` run through every layer. An attention layer writes
    their key and value rows and attends over the cached positions its
    block table covers (their own included), ``qb`` queries at a time;
    a DeltaNet layer takes the session's slot (zero state and tail at
    ``pos0 = 0``) through the chunked form and writes it back. ``ints``
    = ``[user row (negative: none), pos0, valid tokens, item ids x C,
    the attention kind's cache rows x C and block table, the DeltaNet
    kind's slot id]``. Returns ``X`` with the final-normed hidden state
    of the chunk's last valid token in the user's row, the pool, and
    that state."""
    import jax
    import jax.numpy as jnp

    pos0, n_valid = ints[1], ints[2]
    tok = ints[3:3 + C]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < n_valid
    slot = _slot(ints, layout, spec)
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    for i in range(spec.n_layers):
        j = spec.index_in_kind(i)
        with jax.named_scope(_scope(spec, i)):
            h = rms0(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            if spec.pattern[i]:
                wrow, _, table = smallthinker._kind_ints(
                    ints, layout, spec.kind_of(i), C)
                q, k, v, gate = attn_project(theta, i, h, pos, spec)
                pool = _write_rows(pool, j, k, v, wrow, bs)
                o = smallthinker.chunk_attend(
                    q, pool["k"][j], pool["v"][j], table, None, pos, pos0,
                    n_valid, None, spec, qb)
                y = _mm(o.reshape(C, -1) * jax.nn.sigmoid(gate),
                        theta[f"l{i}_wo"], spec)
            else:
                state, tail = _slot_arrays(pool, j, slot, pos0 == 0)
                y, state, tail = gdn_chunk(theta, i, h, state, tail,
                                           n_valid, spec)
                pool = _write_slots(pool, j, slot, state, tail)
            x = x + y
        with jax.named_scope("lin/moe"):
            h2 = rms0(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            routed, shared, *_ = experts(theta, i, h2, valid, spec)
            x = x + routed + shared
    h_last = rms0(jnp.take(x, jnp.maximum(n_valid - 1, 0), axis=0),
                  theta["ln_f_g"], spec.norm_eps)
    return X.at[_user_rows(ints[0], X.shape[0])].set(
        h_last.astype(X.dtype), mode="drop"), pool, h_last


def extend_step(theta, X, seen_bits, pool, Y, ints, *, spec: LinSpec,
                kb: int, T: int, S: int, bs: int, n_items: int, mode: str,
                layout: Tuple, audit: bool = False):
    """One dispatch of the session lane: ``B`` queries, each appending
    up to ``T`` events to its own session and asking for its top
    ``kb``. ``ints: [B, ...]`` int32 rows ``[user row (negative: none,
    nothing is written for it), cached length, new events, item ids x
    T, the attention kind's cache rows to write x T and block table,
    the DeltaNet kind's slot id]``. An attention layer's new rows
    attend over the session's cached rows (the paged kernel on a TPU)
    joined with the new rows themselves, then their keys and values are
    written; a DeltaNet layer advances the session's slot by the
    recurrent form over the valid rows and writes it back (a padded
    query row names slot 0, which nobody holds). Returns the packed
    top-k, the new ``X``, ``seen_bits``, the pool and, compiled with
    ``audit``, what a check compares (else None): every item's
    ``scores`` ``[B, items]`` and, for each row's last new event,
    ``layers`` ``[n_layers, B, D]`` (the residual stream after every
    layer), ``k`` / ``v`` ``[attention layers, B, kv_width]`` (the cache
    rows written for it, as the cache holds them), ``og`` ``[attention
    layers, B, H x d]`` (the factor its attention's output was
    multiplied by: the sigmoid of its gate), ``picks`` / ``gates``
    ``[n_layers, B, k]``, ``h2`` ``[n_layers, B, D]`` (the router's
    picks, their weights and its input) and ``sg`` ``[n_layers, B]``
    (the shared expert's gate), ``mid`` ``[n_layers, B, D]`` (the
    stream behind each layer's mixer, before its experts), ``rows``
    ``[n_layers, B, T, D]`` (the stream after every layer for EVERY new
    row: what the next layer was given) and ``new`` ``[1, B]`` (the
    query's new events). Four float32 counters ride as int32
    bits behind the packed columns: cache rows the attention layers had
    to read (a query's cached rows and its new ones, summed over those
    layers), the held experts a valid token picked summed over layers,
    the (valid token, pick) pairs that fell on a held expert, and the
    pairs the router made (valid tokens x experts a token x layers)."""
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
    slot = _slot(ints, layout, spec)
    with jax.named_scope("lin/embed"):
        x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    kept: Dict[str, list] = {k: [] for k in (
        "layers", "k", "v", "og", "picks", "gates", "h2", "sg", "mid",
        "rows")}
    read = touched = found = jnp.float32(0)
    take_last = lambda a: jnp.take_along_axis(  # noqa: E731
        a, last.reshape((B, 1) + (1,) * (a.ndim - 2)), axis=1)[:, 0]
    for i in range(spec.n_layers):
        j = spec.index_in_kind(i)
        with jax.named_scope(_scope(spec, i)):
            h = rms0(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            if spec.pattern[i]:
                wrow, _, table = smallthinker._kind_ints(
                    ints, layout, spec.kind_of(i), T)
                q, k, v, gate = attn_project(
                    theta, i, h.reshape(B * T, D), tpos.reshape(-1), spec)
                k, v = k.reshape(B, T, KV, d), v.reshape(B, T, KV, d)
                pk, pv = pool["k"][j], pool["v"][j]
                own_ok = (tpos[:, None, :] <= tpos[:, :, None]) \
                    & tvalid[:, None, :]
                o = sdar.attend(q.reshape(B, T, H, d), k.astype(pk.dtype),
                                v.astype(pv.dtype), own_ok, pk, pv, table,
                                len0, spec)
                og = jax.nn.sigmoid(gate)
                y = _mm(o.reshape(B * T, -1) * og, theta[f"l{i}_wo"],
                        spec).reshape(B, T, D)
                pool = _write_rows(pool, j, k, v, wrow.reshape(-1), bs)
                read += jnp.sum(jnp.where(live, len0 + n_new, 0))
                if audit:
                    with jax.named_scope("lin/audit"):
                        held = lambda a, p=pk: take_last(  # noqa: E731
                            a.reshape(B, T, -1)).astype(p.dtype).astype(
                                jnp.float32)
                        kept["k"].append(held(k))
                        kept["v"].append(held(v))
                        kept["og"].append(take_last(og.reshape(B, T, -1)))
            else:
                state, tail = _slot_arrays(pool, j, slot, len0 == 0)
                y, state, tail = gdn_step(theta, i, h, state, tail, n_new,
                                          spec)
                pool = _write_slots(pool, j, slot, state, tail)
            x = x + y
        if audit:
            with jax.named_scope("lin/audit"):
                kept["mid"].append(take_last(x))
        with jax.named_scope("lin/moe"):
            h2 = rms0(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            routed, shared, e, w, n, n_here, sg = experts(
                theta, i, h2.reshape(B * T, D), tvalid.reshape(-1), spec)
            x = x + (routed + shared).reshape(B, T, D)
        touched += n
        found += n_here
        if audit:
            with jax.named_scope("lin/audit"):
                kept["rows"].append(x)
                kept["layers"].append(take_last(x))
                kept["picks"].append(take_last(e.reshape(B, T, -1)))
                kept["gates"].append(take_last(w.reshape(B, T, -1)))
                kept["h2"].append(take_last(h2))
                kept["sg"].append(take_last(sg.reshape(B, T)))
    with jax.named_scope("lin/head"):
        made = jnp.sum(tvalid) * jnp.float32(spec.per_token * spec.n_layers)
        counts = jnp.stack([read, touched, found, made])
        # the head takes a plain final weight: the zero-centred one + 1
        packed, X, seen_bits, scores = score_head(
            dict(theta, ln_f_g=1.0 + theta["ln_f_g"]), X, seen_bits, Y,
            jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], uid,
            n_new, tok, tvalid, counts, eps=spec.norm_eps, kb=kb,
            n_items=n_items, mode=mode, mask_seen=True)
    if not audit:
        return packed, X, seen_bits, pool, None
    return packed, X, seen_bits, pool, dict(
        {k: jnp.stack(v) for k, v in kept.items()}, scores=scores,
        new=n_new[None])
