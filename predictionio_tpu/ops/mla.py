"""GLM-5's block (``model_type: glm_moe_dsa``) for the sequence lane:
multi-head LATENT attention, the learned sparse-attention INDEXER, a
sigmoid-routed expert layer of which this chip holds a share, and the
two programs that serve it from per-user caches.

Per token the cache holds, for every layer, the latent ``(ckv, kr)``
(``kv_lora_rank + qk_rope_head_dim`` values: 576 as published) and
the indexer's key ``ki`` (``index_head_dim``: 128). A query scores its
indexer heads against every cached ``ki`` of its own history, keeps
the ``index_topk`` best positions and attends over those alone.

Latent attention comes in two forms that give the same numbers:

- EXPANDED (:func:`glm_layer`, the full forward pass the trainer and
  the encoder run): keys and values are expanded out of the latents
  (``W_kvb ckv``) and attention is the usual one under the indexer's
  mask;
- ABSORBED (:func:`extend_step`, :func:`prefill_chunk`, the served
  programs): ``W_kvb``'s key half is multiplied into the query and its
  value half into the output, and attention runs over the latents as
  they lie in the cache. :func:`extend_step` cuts and attends through
  :func:`mla_select_attend`, the lane's only attend: a loop over the
  VALID new tokens that takes one token's cut (:func:`index_cut`, a
  kernel: its ``index_topk`` best positions out of its index scores)
  and gathers and attends its selected latents (operands: the absorbed
  queries, the index scores, the block tables, the layer's pool, the
  new events a query brought), so that a padded token row is neither
  cut nor attended;
  :func:`prefill_chunk` masks a dense product a block of queries at a
  time (2,048 tokens a chunk, where a gather of 2,048 latents a token
  would not fit).

The float32 reference of the same equations is
``ops/glm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

LANES = 128
COUNTERS = 4    # float32 counters behind extend_step's packed columns


@dataclasses.dataclass(frozen=True)
class GlmSpec:
    """What of ``SeqRecParams`` shapes the ``glm_moe_dsa`` programs."""

    n_layers: int
    n_dense: int          # leading dense layers (first_k_dense_replace)
    width: int
    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    idx_heads: int
    idx_dim: int
    idx_topk: int
    dense_width: int
    expert_width: int
    n_experts: int        # the router's outputs (published count)
    per_token: int
    n_shared: int
    route_scale: float
    held: int             # experts this chip holds ...
    first: int            # ... from this one on
    norm_eps: float
    rope_theta: float
    compute_dtype: str

    @property
    def lat_width(self) -> int:
        """Values in one cached latent row: ``ckv`` then ``kr``, padded
        to whole 128-lane tiles (576 -> 640 as published: the TPU pads
        the row to that anyway, and a minor dimension that is not a
        whole number of tiles makes it keep a gathered array
        column-major)."""
        return -(-(self.kv_rank + self.d_rope) // LANES) * LANES

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)


def glm_spec(params) -> GlmSpec:
    """``SeqRecParams(block="glm_moe_dsa", ...)`` -> :class:`GlmSpec`."""
    need = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "n_experts", "expert_width",
            "experts_per_token")
    zero = [k for k in need if int(getattr(params, k)) <= 0]
    if zero:
        raise ValueError(f"the glm_moe_dsa block needs {', '.join(zero)}")
    if (params.norm, params.positions, bool(params.tied)) != (
            "rmsnorm", "rope", False):
        raise ValueError(
            "the glm_moe_dsa block takes norm rmsnorm, positions rope "
            "and untied tables (tied false), as GLM-5 publishes it")
    E = int(params.n_experts)
    held = int(params.experts_held) or E
    first = int(params.expert_share) * held
    if first + held > E or int(params.experts_per_token) > E:
        raise ValueError(
            f"share {params.expert_share} of {held} experts does not fit "
            f"the router's {E}")
    if int(params.qk_rope_head_dim) % 2 \
            or int(params.index_head_dim) < int(params.qk_rope_head_dim):
        raise ValueError("the rotated widths: qk_rope_head_dim even and "
                         "at most index_head_dim")
    n_dense = min(int(params.n_dense_layers), int(params.n_layers))
    if n_dense and int(params.dense_width) <= 0:
        raise ValueError("dense layers need dense_width")
    return GlmSpec(
        int(params.n_layers), n_dense, int(params.rank),
        int(params.n_heads), int(params.q_lora_rank),
        int(params.kv_lora_rank), int(params.qk_nope_head_dim),
        int(params.qk_rope_head_dim), int(params.v_head_dim),
        int(params.index_n_heads), int(params.index_head_dim),
        int(params.index_topk), int(params.dense_width),
        int(params.expert_width), E, int(params.experts_per_token),
        int(params.n_shared_experts), float(params.routed_scaling_factor),
        held, first, float(params.norm_eps), float(params.rope_theta),
        str(params.compute_dtype))


# -- parameters ----------------------------------------------------------------

# the suffixes of matmul weights (held in the compute dtype when
# served); norms' gains, the router with its bias and the indexer's
# head weights ``wiw`` stay float32
LOW_SUFFIXES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wiq", "wik",
                "w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down",
                "ws_gate", "ws_up", "ws_down", "item_emb", "out_emb")


def is_low(name: str) -> bool:
    return name.split("_", 1)[-1] in LOW_SUFFIXES or name in LOW_SUFFIXES


def theta_shapes(V: int, spec: GlmSpec
                 ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter in drawing order, in
    ``ops/seqrec.py::_theta_shapes``'s form. The router's bias is
    DRAWN (0.1 x a normal): the published checkpoint's is learned and
    non-zero, and a zero one would leave the rule that it moves the
    choice and not the weight unexercised."""
    D, H = spec.width, spec.n_heads
    out: List[Tuple[str, Tuple[int, ...], Any]] = [
        ("item_emb", (V, D), ("div", math.sqrt(D))), ("ln_f_g", (D,), 1.0)]

    def w(name, shape):
        out.append((name, shape, ("div", math.sqrt(shape[-2]))))

    for i in range(spec.n_layers):
        p = f"l{i}_"
        w(p + "wq_a", (D, spec.q_rank))
        w(p + "wq_b", (spec.q_rank, H * (spec.d_nope + spec.d_rope)))
        w(p + "wkv_a", (D, spec.kv_rank + spec.d_rope))
        w(p + "wkv_b", (spec.kv_rank, H * (spec.d_nope + spec.d_v)))
        w(p + "wo", (H * spec.d_v, D))
        w(p + "wiq", (spec.q_rank, spec.idx_heads * spec.idx_dim))
        w(p + "wik", (D, spec.idx_dim))
        w(p + "wiw", (D, spec.idx_heads))
        if i < spec.n_dense:
            w(p + "w_gate", (D, spec.dense_width))
            w(p + "w_up", (D, spec.dense_width))
            w(p + "w_down", (spec.dense_width, D))
        else:
            F, Fs = spec.expert_width, spec.expert_width * spec.n_shared
            w(p + "router", (D, spec.n_experts))
            out.append((p + "router_b", (spec.n_experts,), ("mul", 0.1)))
            w(p + "we_gate", (spec.held, D, F))
            w(p + "we_up", (spec.held, D, F))
            w(p + "we_down", (spec.held, F, D))
            if Fs:
                w(p + "ws_gate", (D, Fs))
                w(p + "ws_up", (D, Fs))
                w(p + "ws_down", (Fs, D))
        for g, n in (("ln1_g", D), ("ln2_g", D), ("qa_g", spec.q_rank),
                     ("kva_g", spec.kv_rank), ("ik_g", spec.idx_dim)):
            out.append((p + g, (n,), 1.0))
        out.append((p + "ik_b", (spec.idx_dim,), 0.0))
    out.append(("out_emb", (V, D), ("div", math.sqrt(D))))
    return out


def draw_serving_theta(V: int, params, skip: Tuple[str, ...] = ()):
    """The seeded parameters ``init_theta_device`` draws (same keys,
    same order), drawn ON THE DEVICE straight into the dtype each is
    served in (:func:`draw_shapes`)."""
    spec = glm_spec(params)
    return draw_shapes(theta_shapes(V, spec), int(params.seed),
                       spec.n_layers, spec.compute_dtype, is_low, skip)


def draw_value(key, shape, init):
    """One drawn parameter, float32, by its ``init`` (``theta_shapes``'s
    form): ``("div", x)`` / ``("mul", x)`` a normal draw divided /
    multiplied by ``x``; ``("log_uniform", lo, hi)`` the LOG of a
    uniform draw on ``[lo, hi)`` (a decay rate's ``A_log``);
    ``("softplus_inv_log_uniform", lo, hi)`` the inverse softplus of a
    draw log-uniform on ``[lo, hi)`` (a step's ``dt_bias``)."""
    import jax
    import jax.numpy as jnp

    kind = init[0]
    if kind in ("div", "mul"):
        z = jax.random.normal(key, shape)
        return z / init[1] if kind == "div" else z * init[1]
    lo, hi = float(init[1]), float(init[2])
    u = jax.random.uniform(key, shape)
    if kind == "log_uniform":
        return jnp.log(lo + u * (hi - lo))
    if kind == "softplus_inv_log_uniform":
        dt = jnp.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {init!r}")


def draw_shapes(shapes, seed: int, n_layers: int, compute_dtype: str,
                low, skip: Tuple[str, ...] = ()):
    """``shapes`` (``theta_shapes``'s form) drawn on the device from
    ``seed``, a parameter ``low(name)`` says so in the compute dtype,
    one jitted call a layer (layers of one kind share one compiled
    program): billions of parameters never exist in float32 all at
    once, on either side of the bus, and a deploy compiles a handful
    of programs for them, not one an operation."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    drawn = sum(isinstance(s[2], tuple) for s in shapes)
    keys = jax.random.split(jax.random.PRNGKey(int(seed)),
                            max(drawn, 2 + 8 * n_layers))
    keys_host = np.asarray(keys)
    cd = compute_dtype

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw_group(ks, group):
        out, kx = {}, 0
        for name, shape, init, dtype in group:
            if isinstance(init, tuple):
                out[name] = draw_value(ks[kx], shape, init).astype(dtype)
                kx += 1
            else:
                out[name] = jnp.full(shape, init, jnp.float32)
        return out

    groups: Dict[str, list] = {}
    kx = 0
    for name, shape, init in shapes:
        head, _, tail = name.partition("_")
        layer = head if head[:1] == "l" and head[1:].isdigit() else ""
        entry = (tail if layer else name, shape, init,
                 cd if low(name) else "float32")
        key = None
        if isinstance(init, tuple):
            key, kx = kx, kx + 1
        if name not in skip:
            groups.setdefault(layer, []).append((entry, key))
    theta = {}
    for layer, members in groups.items():
        # (indexed on the host: every distinct index of a device array
        # is a compiled slice)
        ks = keys_host[[k for _, k in members if k is not None]]
        out = draw_group(ks, tuple(e for e, _ in members))
        theta.update({(f"{layer}_{n}" if layer else n): v
                      for n, v in out.items()})
    return theta


def serving_theta(theta, spec: GlmSpec) -> Dict[str, Any]:
    """A trained (float32, host or device) ``theta`` as it is served:
    matmul weights and the input table in the compute dtype."""
    import jax.numpy as jnp

    cd = jnp.dtype(spec.compute_dtype)
    return {k: jnp.asarray(v).astype(cd if is_low(k) else jnp.float32)
            for k, v in theta.items()}


# -- pieces --------------------------------------------------------------------

def rms_norm(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope_pairs(x, pos, theta: float):
    """Rotary positions in the INTERLEAVED convention (``rope_interleave:
    true``): the pair ``(x[2i], x[2i + 1])`` turns by ``pos *
    theta^(-2i / d)``. ``x: [..., d]``, ``pos`` of ``x``'s leading
    shape (broadcast over any head axis by the caller)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _mm(a, w, spec: GlmSpec):
    """``a @ w``, operands in the compute dtype, accumulated float32."""
    import jax.numpy as jnp

    if spec.compute_dtype == "float32":
        return a.astype(jnp.float32) @ w.astype(jnp.float32)
    cd = jnp.dtype(spec.compute_dtype)
    return jnp.matmul(a.astype(cd), w.astype(cd),
                      preferred_element_type=jnp.float32)


def _ein(sub: str, a, b, spec: GlmSpec):
    import jax.numpy as jnp

    if spec.compute_dtype == "float32":
        return jnp.einsum(sub, a.astype(jnp.float32), b.astype(jnp.float32))
    cd = jnp.dtype(spec.compute_dtype)
    return jnp.einsum(sub, a.astype(cd), b.astype(cd),
                      preferred_element_type=jnp.float32)


def project(theta, i: int, h, pos, spec: GlmSpec) -> Dict[str, Any]:
    """Everything a layer's attention takes from the normed input
    ``h: [T, D]`` at positions ``pos: [T]``: the query's two parts (the
    rotated one rotated), the latent ``ckv`` (normed) and the shared
    rotated key part ``kr``, and the indexer's queries ``qi``, key
    ``ki`` and head weights ``w``."""
    import jax.numpy as jnp

    p = f"l{i}_"
    T, H = h.shape[0], spec.n_heads
    cq = rms_norm(_mm(h, theta[p + "wq_a"], spec), theta[p + "qa_g"],
                  spec.norm_eps)
    q = _mm(cq, theta[p + "wq_b"], spec).reshape(
        T, H, spec.d_nope + spec.d_rope)
    q_rope = rope_pairs(q[..., spec.d_nope:], pos[:, None],
                        spec.rope_theta)
    kva = _mm(h, theta[p + "wkv_a"], spec)
    ckv = rms_norm(kva[:, :spec.kv_rank], theta[p + "kva_g"],
                   spec.norm_eps)
    kr = rope_pairs(kva[:, spec.kv_rank:], pos, spec.rope_theta)
    r = spec.d_rope
    qi = _mm(cq, theta[p + "wiq"], spec).reshape(
        T, spec.idx_heads, spec.idx_dim)
    qi = jnp.concatenate(
        [rope_pairs(qi[..., :r], pos[:, None], spec.rope_theta),
         qi[..., r:]], axis=-1)
    ki = layer_norm(_mm(h, theta[p + "wik"], spec), theta[p + "ik_g"],
                    theta[p + "ik_b"], spec.norm_eps)
    ki = jnp.concatenate(
        [rope_pairs(ki[:, :r], pos, spec.rope_theta), ki[:, r:]], axis=-1)
    w = (h.astype(jnp.float32) @ theta[p + "wiw"].astype(jnp.float32)) \
        / math.sqrt(spec.idx_heads * spec.idx_dim)
    return {"q_nope": q[..., :spec.d_nope], "q_rope": q_rope, "ckv": ckv,
            "kr": kr, "qi": qi, "ki": ki, "w": w}


def index_scores(qi, w, ki, spec: GlmSpec):
    """``I[.., t, s] = sum_j w[.., t, j] relu(qi[.., t, j] . ki[.., s])``
    (float32). ``qi: [.., T, J, d]``, ``w: [.., T, J]``, ``ki: [.., S,
    d]``."""
    import jax
    import jax.numpy as jnp

    dots = _ein("...tjd,...sd->...tjs", qi, ki, spec)
    return jnp.sum(jax.nn.relu(dots) * w[..., None].astype(jnp.float32),
                   axis=-2)


def kvb_halves(theta, i: int, spec: GlmSpec):
    """``W_kvb`` as its key half ``[R, H, d_nope]`` and value half
    ``[R, H, d_v]``."""
    w = theta[f"l{i}_wkv_b"].reshape(spec.kv_rank, spec.n_heads,
                                     spec.d_nope + spec.d_v)
    return w[..., :spec.d_nope], w[..., spec.d_nope:]


def feed_forward(theta, i: int, h, spec: GlmSpec):
    """The layer's feed-forward on normed ``h: [T, D]``: the gated
    dense one in the leading layers; elsewhere the held share of the
    routed experts plus the shared expert, once. Returns ``(y, (picks
    [T, k], their weights [T, k], local [T, k]) | None)``."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    p = f"l{i}_"

    def gated(x, g, u, d):
        a = jax.nn.silu(_mm(x, theta[p + g], spec)) \
            * _mm(x, theta[p + u], spec)
        return _mm(a, theta[p + d], spec)

    if i < spec.n_dense:
        return gated(h, "w_gate", "w_up", "w_down"), None
    cd = jnp.dtype(spec.compute_dtype)
    with jax.named_scope("moe/router"):
        _, experts, weights = moe.route_sigmoid(
            h, theta[p + "router"], theta[p + "router_b"],
            spec.per_token, spec.route_scale)
    y, local, _ = moe.moe_ffn_share(
        h, experts, weights, theta[p + "we_gate"].astype(cd),
        theta[p + "we_up"].astype(cd), theta[p + "we_down"].astype(cd),
        first=spec.first, compute_dtype=cd)
    if spec.n_shared:
        with jax.named_scope("moe/shared"):
            y = y + gated(h, "ws_gate", "ws_up", "ws_down")
    return y, (experts, weights, local)


def kth_largest(x, k: int):
    """The ``k``-th largest value of each row of ``x`` (float32, -inf
    allowed), exactly, WITHOUT a sort: the floats are mapped to
    unsigned integers of the same order and the answer is built bit by
    bit from the top, one count over the row a bit (32 passes of
    compares, where a top-k of 2,048 out of 65,536 sorts)."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(0x80000000)
    key = jnp.where(u >= top, ~u, u | top)     # order of the floats

    def step(i, ans):
        cand = ans | (top >> i.astype(jnp.uint32))
        n = jnp.sum(key >= cand[..., None], axis=-1)
        return jnp.where(n >= k, cand, ans)

    ans = jax.lax.fori_loop(0, 32, step,
                            jnp.zeros(x.shape[:-1], jnp.uint32))
    back = jnp.where(ans >= top, ans & ~top, ~ans)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def _select_mask(I, allowed, k: int):
    """The indexer's cut as a mask: the ``k`` largest allowed scores of
    each row (all of them where fewer are allowed; every score tied
    with the ``k``-th)."""
    import jax.numpy as jnp

    I = jnp.where(allowed, I, -jnp.inf)
    if k >= I.shape[-1]:
        return allowed
    return allowed & (I >= kth_largest(I, k)[..., None])


# -- the full forward pass (expanded form): trainer and encoder -----------------

def glm_layer(theta, i: int, x, seg, pos, spec: GlmSpec):
    """One layer over whole rows ``x: [B, L, D]``: position ``t`` sees
    the ``index_topk`` positions ``s <= t`` of its own segment that its
    indexer scores highest. Keys and values are EXPANDED from the
    latents. The selection carries no gradient (the published model
    trains its indexer with a loss of its own, which the sequence lane
    does not model)."""
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    H = spec.n_heads
    h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
    p = project(theta, i, h.reshape(B * L, D), pos.reshape(-1), spec)
    p = {k: v.reshape((B, L) + v.shape[1:]) for k, v in p.items()}
    allowed = (seg[:, :, None] == seg[:, None, :]) & (
        jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])
    with jax.named_scope("sess/index"):
        I = index_scores(p["qi"], p["w"], p["ki"], spec)
    with jax.named_scope("sess/select"):
        sel = jax.lax.stop_gradient(_select_mask(I, allowed,
                                                 spec.idx_topk))
    with jax.named_scope("sess/attend"):
        kv = _mm(p["ckv"], theta[f"l{i}_wkv_b"], spec).reshape(
            B, L, H, spec.d_nope + spec.d_v)
        s = (_ein("bthd,bshd->bhts", p["q_nope"], kv[..., :spec.d_nope],
                  spec)
             + _ein("bthd,bsd->bhts", p["q_rope"], p["kr"], spec)) \
            * spec.scale
        a = jax.nn.softmax(jnp.where(sel[:, None], s, -jnp.inf), axis=-1)
        o = _ein("bhts,bshd->bthd", a, kv[..., spec.d_nope:], spec)
        x = x + _mm(o.reshape(B * L, H * spec.d_v), theta[f"l{i}_wo"],
                    spec).reshape(B, L, D)
    with jax.named_scope("sess/moe"):
        h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
        y, _ = feed_forward(theta, i, h2.reshape(B * L, D), spec)
    return x + y.reshape(B, L, D)


# -- the served programs (absorbed form, over the block cache) -------------------

def cache_rows(p: Dict[str, Any], spec: GlmSpec, dtype):
    """A token's two cache rows: ``[ckv | kr | 0-pad]`` and ``ki``."""
    import jax.numpy as jnp

    T = p["ckv"].shape[0]
    pad = spec.lat_width - spec.kv_rank - spec.d_rope
    lat = jnp.concatenate(
        [p["ckv"], p["kr"], jnp.zeros((T, pad), jnp.float32)], axis=-1)
    return lat.astype(dtype), p["ki"].astype(dtype)


def absorbed_query(theta, i: int, p: Dict[str, Any], spec: GlmSpec):
    """``[q_nope W_kvb^K | q_rope | 0-pad]``: the query in the space of
    a cached latent row, ``[T, H, lat_width]``."""
    import jax.numpy as jnp

    wk, _ = kvb_halves(theta, i, spec)
    qa = _ein("thd,rhd->thr", p["q_nope"], wk, spec)
    T, H = qa.shape[:2]
    pad = spec.lat_width - spec.kv_rank - spec.d_rope
    return jnp.concatenate(
        [qa, p["q_rope"], jnp.zeros((T, H, pad), jnp.float32)], axis=-1)


def absorbed_output(theta, i: int, ol, spec: GlmSpec):
    """The attention-weighted latents ``ol: [T, H, >= kv_rank]``
    through ``W_kvb``'s value half and ``W_o``: ``[T, D]``."""
    _, wv = kvb_halves(theta, i, spec)
    o = _ein("thr,rhv->thv", ol[..., :spec.kv_rank], wv, spec)
    return _mm(o.reshape(o.shape[0], -1), theta[f"l{i}_wo"], spec)


def _shift_left(a, s: int, lane):
    """``out[x] = a[x + s]`` in the flat order of a ``[R, 128]`` tile
    (circular): whole rows by a sublane rotation, lanes by a lane
    rotation whose wrapped lanes come from the next row."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    R = a.shape[0]
    if s % LANES == 0:
        return pltpu.roll(a, (R - s // LANES) % R, 0)
    y = pltpu.roll(a, LANES - s, 1)
    return jnp.where(lane < LANES - s, y, pltpu.roll(y, R - 1, 0))


def _index_cut_kernel(sc_ref, rows_ref, idx_ref, out_ref, *, K: int):
    """One token row's cut, whole in VMEM. ``sc_ref [R, 128]`` float32
    (position ``128 r + l``), ``rows_ref`` the pool row behind each
    position; ``idx_ref`` / ``out_ref`` ``[>= K / 128, 128]``: the kept
    positions in rising order (-1 past the last) and their pool rows.

    Three steps, none a sort: (1) the ``K``-th largest score as
    :func:`kth_largest` finds it (the floats as integers of the same
    order, the answer a bit a counting pass), and where scores tie
    with it the tied positions' lowest, by 17 more counting passes
    over the positions; (2) every kept position's rank, by two
    products with triangles of ones (counts within a 128-lane row,
    then over the rows before); (3) each kept position moved left by
    the number of holes before it, a bit of that distance a pass,
    lowest bit first, which keeps their order and lets no two
    meet."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    lax = jax.lax
    R = sc_ref.shape[0]
    n_out = idx_ref.shape[0]
    i32 = jnp.int32
    sign, none = i32(-2 ** 31), i32(0x807FFFFF - 2 ** 32)   # (-inf's key)
    bits = pltpu.bitcast(sc_ref[...], i32)
    key = jnp.where(bits >= 0, bits, bits ^ i32(0x7FFFFFFF))
    lane = lax.broadcasted_iota(i32, (R, LANES), 1)
    pos = lax.broadcasted_iota(i32, (R, LANES), 0) * LANES + lane

    def count(mask):
        return jnp.sum(mask.astype(jnp.float32)).astype(i32)

    def score_bit(i, ans):
        cand = ans | lax.shift_left(i32(1), i32(31) - i)
        return jnp.where(count(key >= (cand ^ sign)) >= K, cand, ans)

    kth = lax.fori_loop(0, 32, score_bit, i32(0)) ^ sign
    above = key > jnp.maximum(kth, none)
    tied = (key == kth) & (kth > none)
    room = K - count(above)

    def position_bit(i, ans):
        cand = ans | lax.shift_left(i32(1), i32(16) - i)
        return jnp.where(count(tied & (pos < cand)) <= room, cand, ans)

    keep = above | (tied & (pos < lax.fori_loop(0, 17, position_bit,
                                                i32(0))))
    ones = keep.astype(jnp.bfloat16)
    upto = (lax.broadcasted_iota(i32, (LANES, LANES), 0)
            <= lax.broadcasted_iota(i32, (LANES, LANES), 1))
    in_row = jnp.dot(ones, upto.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    rows_before = (lax.broadcasted_iota(i32, (R, R), 1)
                   < lax.broadcasted_iota(i32, (R, R), 0))
    before = jnp.dot(
        rows_before.astype(jnp.bfloat16),
        jnp.broadcast_to(in_row[:, LANES - 1:], (R, LANES)).astype(
            jnp.bfloat16), preferred_element_type=jnp.float32)
    rank = (before + in_row).astype(i32) - 1
    at = jnp.where(keep, pos, -1)
    holes = jnp.where(keep, pos - rank, 0)
    rows = rows_ref[...]
    s = 1
    while s < R * LANES:
        moves = (at >= 0) & ((holes & s) != 0)
        lands = _shift_left(moves.astype(i32), s, lane) != 0
        at = jnp.where(lands, _shift_left(at, s, lane),
                       jnp.where(moves, -1, at))
        holes = jnp.where(lands, _shift_left(holes, s, lane), holes)
        rows = jnp.where(lands, _shift_left(rows, s, lane), rows)
        s *= 2
    idx_ref[...] = at[:n_out]
    out_ref[...] = jnp.where(at[:n_out] >= 0, rows[:n_out], 0)


def index_cut(scores, rows, K: int, *, interpret: bool):
    """The indexer's cut of ONE token row, exactly: ``scores [S]``
    float32 (-inf: not eligible), ``rows [S]`` int32 (the pool row
    behind each position) -> ``(idx [K], kept [K])``: the positions of
    the ``K`` largest scores in rising order (fewer where fewer are
    eligible, then -1; of the scores tied with the ``K``-th the lowest
    positions) and their pool rows (0 past the last). A Pallas TPU
    kernel (:func:`_index_cut_kernel`; ``interpret``: off the TPU): a sort
    of one row costs the chip what a sort of eight does, and XLA's
    counting passes and compaction a fusion each (PERF.md section 6,
    PR 34)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    S = scores.shape[0]
    n_out = -(-K // LANES)
    # whole float32 tiles of 8 x 128, and room for the K outputs
    padded = -(-max(S, n_out * LANES) // (8 * LANES)) * 8 * LANES
    scores = jnp.pad(scores.astype(jnp.float32), (0, padded - S),
                     constant_values=-jnp.inf)
    rows = jnp.pad(rows.astype(jnp.int32), (0, padded - S))
    shape = jax.ShapeDtypeStruct((n_out, LANES), jnp.int32)
    idx, kept = pl.pallas_call(
        functools.partial(_index_cut_kernel, K=K), out_shape=(shape, shape),
        interpret=interpret, name="dsa_index_cut",
    )(scores.reshape(-1, LANES), rows.reshape(-1, LANES))
    return idx.reshape(-1)[:K], kept.reshape(-1)[:K]


def mla_select_attend(qf, I, table, pool, n_new, *, spec: GlmSpec,
                      bs: int, audit: bool = False):
    """:func:`_select_attend` as ONE jitted function of its shapes: a
    program's layers then share one trace and one lowering of the loop
    and its kernel (a layer's cost the host 0.06 s to trace and lower,
    and a deploy lowers twelve programs of six layers)."""
    import jax

    # (off the TPU the kernel runs interpreted)
    return _select_attend_program(
        spec, bs, audit, jax.default_backend() != "tpu")(
            qf, I, table, pool, n_new)


@functools.lru_cache(maxsize=None)
def _select_attend_program(spec: GlmSpec, bs: int, audit: bool,
                           interpret: bool):
    import jax

    return jax.jit(functools.partial(
        _select_attend, spec=spec, bs=bs, audit=audit, interpret=interpret))


def _select_attend(qf, I, table, pool, n_new, *, spec: GlmSpec, bs: int,
                   audit: bool, interpret: bool):
    """The indexer's cut and the attention over what it keeps, for the
    VALID token rows of ``B x T`` alone: ``qf [B, T, H, lat_width]``
    (the absorbed queries), ``I [B, T, S]`` float32 (a token's index
    scores over its session's positions, -inf where it may not look),
    ``table [B, S / bs]`` int32 (the pool block behind each ``bs``
    positions of a query's session), ``pool [rows, lat_width]`` (the
    layer's latent rows AFTER this dispatch's were written), ``n_new
    [B]``. Returns ``(out, kept, selected)``: ``out [B, T, H,
    lat_width]`` float32, ``softmax(scale qf . rows) rows`` over the
    ``index_topk`` best-scored positions of each row (all of them
    where fewer have a score) in ``extend_step``'s precision, and
    ZEROS for every token row ``t >= n_new[b]``; ``kept``, the
    positions kept summed over the valid rows (int32); with ``audit``
    ``selected [B, K]``, the positions each query's LAST valid row
    kept (-1: none, all of them for a query that brought nothing),
    else None.

    One loop over the valid token rows (their count is the trip count,
    so a padded row is never cut, looked up, gathered or scored). A
    step cuts its row (scope ``sess/select``: the ``K`` best of ``S``
    scores, exactly, and their pool rows out of the block table) and
    attends it (scope ``sess/attend``: the row's ``K`` latents
    gathered, ``[K, lat_width]``, 2.6 MB as published, and two plain
    matrix products ``[H, width] x [width, K]``); the two scopes stand
    side by side, never one inside the other, so that a trace books
    the cut to the selection and the gather to the attention.

    It is XLA's own gather and not a Pallas kernel because the chip's
    compiler refuses a copy out of a tiled HBM array that is not whole
    tiles of 8 rows (PERF.md section 6, PR 31); how a row is cut was
    settled on the chip (PERF.md section 6, PR 34)."""
    import jax
    import jax.numpy as jnp

    B, T, H, W = qf.shape
    S = I.shape[-1]
    K = min(spec.idx_topk, S)
    qf = qf.reshape(B * T, H, W)
    I = I.reshape(B * T, S)
    valid = (jnp.arange(T)[None, :] < n_new[:, None]).reshape(-1)
    order = jnp.argsort(~valid, stable=True)        # the valid rows first
    with jax.named_scope("sess/select"):
        # the pool row behind every position of a QUERY's session
        rows_of = (table[:, :, None] * bs
                   + jnp.arange(bs, dtype=jnp.int32)).reshape(B, S)

    def one(i, carry):
        out, kept, selected = carry
        r = order[i]
        b = r // T
        row = lambda a, j=r: jax.lax.dynamic_index_in_dim(  # noqa: E731
            a, j, keepdims=False)
        with jax.named_scope("sess/select"):
            idx, phys = index_cut(row(I), row(rows_of, b), K,
                                  interpret=interpret)
            ok = idx >= 0
            kept = kept + jnp.sum(ok)
            if audit:
                selected = jax.lax.dynamic_update_index_in_dim(
                    selected, jnp.where(r % T == n_new[b] - 1, idx,
                                        row(selected, b)), b, 0)
        with jax.named_scope("sess/attend"):
            g = jnp.take(pool, phys, axis=0, mode="clip")       # [K, W]
            s = _ein("hc,kc->hk", row(qf), g, spec) * spec.scale
            a = jax.nn.softmax(jnp.where(ok[None, :], s, -jnp.inf), axis=-1)
            out = jax.lax.dynamic_update_index_in_dim(
                out, _ein("hk,kc->hc", a, g, spec), r, 0)
        return out, kept, selected

    out, kept, selected = jax.lax.fori_loop(
        0, jnp.sum(valid), one,
        (jnp.zeros((B * T, H, W), jnp.float32), jnp.int32(0),
         jnp.full((B, K) if audit else (), -1, jnp.int32)))
    return out.reshape(B, T, H, W), kept, selected if audit else None


def _new_bits(tok, valid, words: int):
    """The seen-bitmap words of a row's new events: ``tok: [T]`` item
    positions -> ``[words]`` int32 with their bits set."""
    import jax.numpy as jnp

    hit = (jnp.arange(words, dtype=jnp.int32)[None, :]
           == (tok >> 5)[:, None]) & valid[:, None]
    add = jnp.where(hit, jnp.left_shift(jnp.int32(1), tok & 31)[:, None], 0)
    out = add[0]
    for t in range(1, add.shape[0]):
        out = out | add[t]
    return out


def _user_rows(uid, n_rows: int):
    """Where a query row writes its user's state: its user row, or
    past the table (a scatter in mode ``drop`` then writes nothing)
    for a row that has none (negative: padding, a prefill chunk that
    is not a history's last)."""
    import jax.numpy as jnp

    return jnp.where(uid < 0, n_rows, uid)


def score_head(theta, X, seen_bits, Y, x_last, uid, n_new, tok, tvalid,
               counts, *, eps: float, kb: int, n_items: int, mode: str,
               mask_seen: bool):
    """The end of an extend dispatch, whatever the backbone: ``x_last
    [B, D]`` (the residual stream at each query's last new event)
    through the final norm into the users' rows of ``X``, the scores
    against ``Y``, the seen mask (the new events ``tok`` under
    ``tvalid`` marked first) and the top ``kb``, packed with ``counts``
    (float32 counters, the same in every row, as int32 bits behind the
    ``2 kb`` result columns: one fetch brings everything). A query
    without new events scores its stored row. Returns ``(packed, X,
    seen_bits, every item's scores before any mask)``."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.als_pallas import unpack_seen_bits
    from predictionio_tpu.ops.serving import _pack, _score_einsum

    B = uid.shape[0]
    h_new = rms_norm(x_last, theta["ln_f_g"], eps)
    h_old = jnp.take(X, uid, axis=0, mode="clip").astype(jnp.float32)
    if X.dtype == jnp.bfloat16:
        # round HERE, by an operation the compiler may not elide:
        # with excess precision allowed it scored the unrounded
        # state while the store kept the rounded one, and the same
        # prefix asked again (no new events: the stored row) gave
        # other scores (my chip run, PR 30)
        h_new = jax.lax.reduce_precision(h_new, exponent_bits=8,
                                         mantissa_bits=7)
    hq = jnp.where((n_new > 0)[:, None], h_new, h_old).astype(X.dtype)
    urow = _user_rows(uid, X.shape[0])
    X = X.at[urow].set(hq, mode="drop")
    scores = _score_einsum("mr,br->bm", Y, hq.astype(Y.dtype), mode=mode)
    masked = jnp.where(jnp.arange(scores.shape[1])[None, :] < n_items,
                       scores, -jnp.inf)
    if mask_seen:
        words = seen_bits.shape[1]
        rows = jnp.take(seen_bits, uid, axis=0, mode="clip") | jax.vmap(
            lambda t, v: _new_bits(t, v, words))(tok, tvalid)
        seen_bits = seen_bits.at[urow].set(rows, mode="drop")
        masked = jnp.where(jax.vmap(
            lambda r: unpack_seen_bits(r, scores.shape[1]))(rows),
            -jnp.inf, masked)
    vals, top = jax.lax.top_k(masked, kb)
    packed = jnp.concatenate(
        [_pack(vals, top), jnp.broadcast_to(
            jax.lax.bitcast_convert_type(counts.astype(jnp.float32),
                                         jnp.int32),
            (B, counts.shape[0]))], axis=-1)
    return packed, X, seen_bits, scores


def extend_step(theta, X, seen_bits, lat, ik, Y, ints, *, spec: GlmSpec,
                kb: int, T: int, S: int, bs: int, n_items: int, mode: str,
                mask_seen: bool, audit: bool = False):
    """One dispatch of the session lane: ``B`` queries, each appending
    up to ``T`` events to its own session and asking for its top
    ``kb``. ``ints: [B, 3 + 2T + S / bs]`` int32 rows ``[user row
    (negative: none, nothing is written for it), cached length, new
    events, item ids x T, cache rows to write x T, the session's block
    table]``; ``lat`` / ``ik``: per layer the
    latent and index-key pools ``[blocks, bs, width]``; ``X``: the
    users' last hidden states (the store's user table); ``Y``: the
    output table. Every layer scores the index keys for the whole
    bucket (``[B, T, S]``, one batched product) and then cuts and
    attends through :func:`mla_select_attend` alone (a padded token
    row is never cut, gathered or scored). Returns the packed top-k,
    the new ``X``,
    ``seen_bits``, ``lat``, ``ik`` and, compiled with ``audit``, what a
    check compares (else None): every item's ``scores`` ``[B, items]``
    and, for each row's last new event, ``layers`` ``[n_layers, B,
    D]`` (the residual stream after every layer), ``selected``
    ``[n_layers, B, K]`` (the positions attended over, -1: none),
    ``lat`` / ``ik`` ``[n_layers, B, width]`` (the two cache rows
    written for it), ``picks`` / ``gates`` ``[n_expert_layers, B, k]``
    and ``h2`` ``[n_expert_layers, B, D]`` (the router's picks, their
    weights and its input). ``COUNTERS`` float32 counters ride as int32 bits
    behind the packed columns: keys selected, keys eligible, router
    picks that fell on a held expert, and held experts that a token
    picked (each over the VALID new tokens and every layer: padded
    token rows route too and count for nothing)."""
    import jax
    import jax.numpy as jnp

    B = ints.shape[0]
    D = spec.width
    uid, len0, n_new = ints[:, 0], ints[:, 1], ints[:, 2]
    tok = ints[:, 3:3 + T]
    wrow = ints[:, 3 + T:3 + 2 * T].reshape(-1)
    table = ints[:, 3 + 2 * T:]
    tpos = len0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    tvalid = jnp.arange(T)[None, :] < n_new[:, None]
    last = jnp.maximum(n_new - 1, 0)
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    lat, ik = list(lat), list(ik)
    kept = {k: [] for k in ("layers", "selected", "lat", "ik", "picks",
                            "gates", "h2")}
    share_n = share_d = local_n = touched = jnp.float32(0)
    s_ar = jnp.arange(S, dtype=jnp.int32)
    for i in range(spec.n_layers):
        with jax.named_scope("sess/project"):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            p = project(theta, i, h.reshape(B * T, D), tpos.reshape(-1),
                        spec)
            rl, rk = cache_rows(p, spec, lat[i].dtype)
            nb = lat[i].shape[0]
            lat_i = lat[i].reshape(nb * bs, -1).at[wrow].set(rl)
            ik_i = ik[i].reshape(nb * bs, -1).at[wrow].set(rk)
            lat[i] = lat_i.reshape(lat[i].shape)
            ik[i] = ik_i.reshape(ik[i].shape)
        with jax.named_scope("sess/index"):
            kis = jnp.take(ik[i], table, axis=0, mode="clip").reshape(
                B, S, -1)
            # a query row at a time: [T, heads, S] products, not B of
            # them at once
            I = jax.lax.map(
                lambda a: index_scores(a[0], a[1], a[2], spec),
                (p["qi"].reshape(B, T, spec.idx_heads, spec.idx_dim),
                 p["w"].reshape(B, T, spec.idx_heads), kis))
            I = jnp.where(s_ar[None, None, :] <= tpos[:, :, None], I,
                          -jnp.inf)
        with jax.named_scope("sess/attend"):
            qf = absorbed_query(theta, i, p, spec).reshape(
                B, T, spec.n_heads, spec.lat_width)
        # (the loop opens its own scopes: its cut is the selection's)
        ol, n_kept, last_kept = mla_select_attend(
            qf, I, table, lat_i, n_new, spec=spec, bs=bs, audit=audit)
        with jax.named_scope("sess/attend"):
            x = x + absorbed_output(
                theta, i, ol.reshape(B * T, spec.n_heads, -1),
                spec).reshape(B, T, D)
        with jax.named_scope("sess/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, routed = feed_forward(theta, i, h2.reshape(B * T, D), spec)
            x = x + y.reshape(B, T, D)
        take_last = lambda a: jnp.take_along_axis(  # noqa: E731
            a, last.reshape((B, 1) + (1,) * (a.ndim - 2)), axis=1)[:, 0]
        eligible = jnp.minimum(tpos + 1, S).astype(jnp.float32)
        share_n += n_kept
        share_d += jnp.sum(jnp.where(tvalid, eligible, 0.0))
        if audit:
            kept["layers"].append(take_last(x))
            kept["selected"].append(last_kept)
            kept["lat"].append(take_last(rl.reshape(B, T, -1)))
            kept["ik"].append(take_last(rk.reshape(B, T, -1)))
        if routed is not None:
            experts, weights, local = (r.reshape(B, T, -1) for r in routed)
            local = local & tvalid[..., None]
            local_n += jnp.sum(local)
            touched += jnp.sum(jnp.zeros((spec.held + 1,), bool).at[
                jnp.where(local, experts - spec.first, spec.held)].set(
                    True)[:spec.held])
            if audit:
                kept["picks"].append(take_last(experts))
                kept["gates"].append(take_last(weights))
                kept["h2"].append(take_last(h2))
    with jax.named_scope("sess/head"):
        counts = jnp.stack([share_n, share_d, local_n, touched]).astype(
            jnp.float32)
        packed, X, seen_bits, scores = score_head(
            theta, X, seen_bits, Y, jnp.take_along_axis(
                x, last[:, None, None], axis=1)[:, 0], uid, n_new, tok,
            tvalid, counts, eps=spec.norm_eps, kb=kb, n_items=n_items,
            mode=mode, mask_seen=mask_seen)
    if not audit:
        return packed, X, seen_bits, tuple(lat), tuple(ik), None
    empty = {"picks": jnp.zeros((0, B, spec.per_token), jnp.int32),
             "gates": jnp.zeros((0, B, spec.per_token), jnp.float32),
             "h2": jnp.zeros((0, B, D), jnp.float32)}
    return packed, X, seen_bits, tuple(lat), tuple(ik), dict(
        {k: jnp.stack(v) if v else empty[k] for k, v in kept.items()},
        scores=scores)


def prefill_chunk(theta, X, lat, ik, ints, *, spec: GlmSpec, C: int,
                  S: int, bs: int, qb: int):
    """One chunk of one session's prefill: ``C`` tokens at positions
    ``pos0 ..`` written to the cache and run through every layer
    against the ``S`` cached positions the block table covers (their
    own included). ``ints: [3 + 2C + S / bs]`` = ``[user row
    (negative: none), pos0, valid tokens, item ids x C, cache rows x
    C, block table]``.
    Attention is the absorbed form, dense under the indexer's mask,
    ``qb`` queries at a time. Returns ``X`` with the final-normed
    hidden state of the chunk's last valid token written to the user's
    row (the caller names none for every chunk but a history's last),
    ``lat``, ``ik`` and that state."""
    import jax
    import jax.numpy as jnp

    D, H = spec.width, spec.n_heads
    pos0, n_valid = ints[1], ints[2]
    tok = ints[3:3 + C]
    wrow = ints[3 + C:3 + 2 * C]
    table = ints[3 + 2 * C:]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    K = min(spec.idx_topk, S)
    s_ar = jnp.arange(S, dtype=jnp.int32)
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    lat, ik = list(lat), list(ik)
    for i in range(spec.n_layers):
        with jax.named_scope("sess/project"):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            p = project(theta, i, h, pos, spec)
            rl, rk = cache_rows(p, spec, lat[i].dtype)
            nb = lat[i].shape[0]
            lat[i] = lat[i].reshape(nb * bs, -1).at[wrow].set(
                rl).reshape(lat[i].shape)
            ik[i] = ik[i].reshape(nb * bs, -1).at[wrow].set(
                rk).reshape(ik[i].shape)
            kis = jnp.take(ik[i], table, axis=0, mode="clip").reshape(S, -1)
            lats = jnp.take(lat[i], table, axis=0, mode="clip").reshape(
                S, -1)
            qf = absorbed_query(theta, i, p, spec)

        def block(args, kis=kis, lats=lats):
            qi_b, w_b, qf_b, pos_b = args
            allowed = s_ar[None, :] <= pos_b[:, None]
            with jax.named_scope("sess/index"):
                I = index_scores(qi_b, w_b, kis, spec)
            with jax.named_scope("sess/select"):
                sel = _select_mask(I, allowed, K)
            with jax.named_scope("sess/attend"):
                s = _ein("qhc,sc->qhs", qf_b, lats, spec) * spec.scale
                a = jax.nn.softmax(
                    jnp.where(sel[:, None, :], s, -jnp.inf), axis=-1)
                return _ein("qhs,sc->qhc", a, lats,
                            spec)[..., :spec.kv_rank]

        def cut(a):
            return a.reshape((C // qb, qb) + a.shape[1:])

        ol = jax.lax.map(block, (cut(p["qi"]), cut(p["w"]), cut(qf),
                                 cut(pos)))
        with jax.named_scope("sess/attend"):
            x = x + absorbed_output(theta, i, ol.reshape(C, H, -1), spec)
        with jax.named_scope("sess/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, _ = feed_forward(theta, i, h2, spec)
            x = x + y
    h_last = rms_norm(jnp.take(x, jnp.maximum(n_valid - 1, 0), axis=0),
                      theta["ln_f_g"], spec.norm_eps)
    return X.at[_user_rows(ints[0], X.shape[0])].set(
        h_last.astype(X.dtype), mode="drop"), tuple(lat), tuple(ik), h_last
