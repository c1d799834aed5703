"""SDAR-30B-A3B's block (``model_type: sdar_moe``) for the sequence
lane: grouped-query attention with per-head QK norms under a
BLOCK-CAUSAL mask, 128 softmax-routed experts of which 8 a token with
renormalised gates, and generation by DIFFUSION OVER BLOCKS: a block
of ``block_len`` positions starts as mask tokens and is denoised in
passes, each pass a forward of the block against everything before it
and itself (bidirectional inside), unmasking the positions the rule
picks; the finished block is committed and the next one starts.

Per token the cache holds, for every layer, the key and the value rows
(``num_key_value_heads x head_dim`` values each: 512 + 512 as
published). A token sees its whole block and every earlier block, so a
block's cache rows depend on all of its tokens and are COMMITTED only
when the block is full; whatever is behind the last full block stays
ids (``ops/sessions.py``: a session's tail).

This file holds the device programs: the full forward
(:func:`sdar_layer`: trainer's encoder, tests), and the three served
ones over the block cache: :func:`prefill_chunk`, :func:`commit_events`
and :func:`slate_round` (the denoising loop of one block for a group of
queries, the commit pass, the scratch writes, the packed result).
``ops/slates.py`` drives them; ``ops/sdar_reference.py`` is the plain
float32 reference of the same equations.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

from predictionio_tpu.ops.attention import PAGED_NEG
from predictionio_tpu.ops.mla import _ein, _mm, _user_rows, rms_norm

REMASKING = ("low_confidence_static", "low_confidence_dynamic")
NEW_EVENTS = 8      # new events a round's first row may mark as seen
COUNTERS = 3        # int32 counters behind slate_round's packed columns


@dataclasses.dataclass(frozen=True)
class SdarSpec:
    """What of ``SeqRecParams`` shapes the ``sdar_moe`` programs."""

    n_layers: int
    width: int
    n_heads: int
    n_kv: int
    head_dim: int
    expert_width: int
    n_experts: int
    per_token: int
    renorm: bool
    norm_eps: float
    rope_theta: float
    compute_dtype: str
    block_len: int
    steps: int
    remasking: str
    threshold: float
    mask_token: int     # the mask token's row; negative: the tables' last

    @property
    def kv_width(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    def mask_row(self, V: int) -> int:
        return self.mask_token if self.mask_token >= 0 else V - 1


def sdar_spec(params) -> SdarSpec:
    """``SeqRecParams(block="sdar_moe", ...)`` -> :class:`SdarSpec`."""
    need = ("n_kv_heads", "head_dim", "n_experts", "expert_width",
            "experts_per_token", "block_length", "denoising_steps")
    zero = [k for k in need if int(getattr(params, k)) <= 0]
    if zero:
        raise ValueError(f"the sdar_moe block needs {', '.join(zero)}")
    if (params.norm, params.positions, bool(params.tied)) != (
            "rmsnorm", "rope", False):
        raise ValueError(
            "the sdar_moe block takes norm rmsnorm, positions rope and "
            "untied tables (tied false), as SDAR publishes it")
    H, KV = int(params.n_heads), int(params.n_kv_heads)
    if H % KV or int(params.head_dim) % 2:
        raise ValueError(f"{H} query heads do not share {KV} key/value "
                         "heads evenly, or head_dim is odd")
    if int(params.experts_per_token) > int(params.n_experts):
        raise ValueError("experts_per_token over n_experts")
    if params.remasking not in REMASKING:
        raise ValueError(f"remasking {params.remasking!r}: one of "
                         f"{REMASKING}")
    return SdarSpec(
        int(params.n_layers), int(params.rank), H, KV, int(params.head_dim),
        int(params.expert_width), int(params.n_experts),
        int(params.experts_per_token), bool(params.norm_topk_prob),
        float(params.norm_eps), float(params.rope_theta),
        str(params.compute_dtype), int(params.block_length),
        int(params.denoising_steps), str(params.remasking),
        float(params.confidence_threshold), int(params.mask_token))


# -- parameters ----------------------------------------------------------------

LOW_SUFFIXES = ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down",
                "item_emb", "out_emb")


def is_low(name: str) -> bool:
    """Matmul weights and the tables are held in the compute dtype when
    served; norms' gains and the router stay float32."""
    return name.split("_", 1)[-1] in LOW_SUFFIXES or name in LOW_SUFFIXES


def theta_shapes(V: int, spec: SdarSpec
                 ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter in drawing order, in
    ``ops/seqrec.py::_theta_shapes``'s form."""
    D, A, KW = spec.width, spec.n_heads * spec.head_dim, spec.kv_width
    E, F = spec.n_experts, spec.expert_width
    out: List[Tuple[str, Tuple[int, ...], Any]] = [
        ("item_emb", (V, D), ("div", math.sqrt(D))), ("ln_f_g", (D,), 1.0)]
    for i in range(spec.n_layers):
        p = f"l{i}_"
        for name, shape in (("wq", (D, A)), ("wk", (D, KW)),
                            ("wv", (D, KW)), ("wo", (A, D)),
                            ("router", (D, E)), ("we_gate", (E, D, F)),
                            ("we_up", (E, D, F)), ("we_down", (E, F, D))):
            out.append((p + name, shape, ("div", math.sqrt(shape[-2]))))
        for g, n in (("ln1_g", D), ("ln2_g", D), ("qn_g", spec.head_dim),
                     ("kn_g", spec.head_dim)):
            out.append((p + g, (n,), 1.0))
    out.append(("out_emb", (V, D), ("div", math.sqrt(D))))
    return out


def draw_serving_theta(V: int, params, skip: Tuple[str, ...] = ()):
    """The seeded parameters ``init_theta_device`` draws (same keys,
    same order), drawn ON THE DEVICE into the dtype each is served in,
    one jitted call a layer (``ops/mla.py::draw_shapes``)."""
    from predictionio_tpu.ops import mla

    spec = sdar_spec(params)
    return mla.draw_shapes(theta_shapes(V, spec), int(params.seed),
                           spec.n_layers, spec.compute_dtype, is_low, skip)


def serving_theta(theta, spec: SdarSpec) -> Dict[str, Any]:
    """A (float32, host or device) ``theta`` as it is served."""
    import jax.numpy as jnp

    cd = jnp.dtype(spec.compute_dtype)
    return {k: jnp.asarray(v).astype(cd if is_low(k) else jnp.float32)
            for k, v in theta.items()}


# -- pieces --------------------------------------------------------------------

def rope_half(x, pos, theta: float):
    """Rotary positions, the half-split (rotate-half) convention of the
    Qwen / Llama family: ``x: [T, heads, d]`` at ``pos: [T]``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def project(theta, i: int, h, pos, spec: SdarSpec, rotate: bool = True):
    """A layer's attention operands from the normed input ``h: [T, D]``
    at positions ``pos: [T]``: queries ``[T, H, d]`` and keys ``[T, KV,
    d]``, each RMS-normed over its ``d`` values with the projection's
    one learned weight (where the model has QK norms: ``qn_g`` /
    ``kn_g`` in ``theta``) and, with ``rotate``, rotated; values ``[T,
    KV, d]``."""
    p = f"l{i}_"
    T, d = h.shape[0], spec.head_dim
    q = _mm(h, theta[p + "wq"], spec).reshape(T, spec.n_heads, d)
    k = _mm(h, theta[p + "wk"], spec).reshape(T, spec.n_kv, d)
    v = _mm(h, theta[p + "wv"], spec).reshape(T, spec.n_kv, d)
    if p + "qn_g" in theta:
        q = rms_norm(q, theta[p + "qn_g"], spec.norm_eps)
        k = rms_norm(k, theta[p + "kn_g"], spec.norm_eps)
    if rotate:
        q = rope_half(q, pos, spec.rope_theta)
        k = rope_half(k, pos, spec.rope_theta)
    return q, k, v


def moe_layer(theta, i: int, h2, valid, spec: SdarSpec, router_input=None,
              activation: str = "silu", first: int = 0):
    """The expert layer on normed ``h2: [T, D]``: softmax over the 128
    router logits in float32, the 8 largest, their weights divided by
    their sum; every expert is held, so this is ``moe_ffn_share`` with
    the whole range local (the served path of the ``glm_moe_dsa`` cell
    too: one dispatch plan, one pair of grouped matmuls, one combine).
    A token row ``valid`` marks False (padding of a query bucket)
    routes NOWHERE: its picks fall past the held range, so no expert's
    weights are read for it. ``router_input``: what the router reads
    where that is not ``h2`` (``ops/smallthinker.py``: the attention's
    input); ``activation``: the experts' gate's; ``first``: the first
    expert of the share ``theta`` holds, where it holds fewer than the
    router chooses among (``ops/qwen3next.py``). Returns ``(y [T, D],
    picks [T, k], gates [T, k], experts that got a row)``."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    p = f"l{i}_"
    cd = jnp.dtype(spec.compute_dtype)
    _, _, experts, weights = moe.route(
        h2 if router_input is None else router_input, theta[p + "router"],
        spec.per_token, renorm=spec.renorm)
    sent = jnp.where(valid[:, None], experts, spec.n_experts)
    y, _, gs = moe.moe_ffn_share(
        h2, sent, weights, theta[p + "we_gate"].astype(cd),
        theta[p + "we_up"].astype(cd), theta[p + "we_down"].astype(cd),
        first=first, compute_dtype=cd, activation=activation)
    return y, experts, weights, jnp.sum(gs > 0)


def block_visible(pos_q, pos_k, block_len: int):
    """Key at ``pos_k`` is visible to the query at ``pos_q``: its block
    is the query's or an earlier one."""
    return pos_k // block_len <= pos_q // block_len


# -- the full forward pass: trainer's encoder, tests ----------------------------

def sdar_layer(theta, i: int, x, seg, pos, spec: SdarSpec):
    """One layer over whole rows ``x: [B, L, D]``: position ``t`` sees
    the positions of its own segment whose block is its own or an
    earlier one (dense masked attention, the KV heads shared by their
    query groups)."""
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    KV, G, d = spec.n_kv, spec.group, spec.head_dim
    with jax.named_scope("sdar/attn"):
        h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
        q, k, v = project(theta, i, h.reshape(B * L, D), pos.reshape(-1),
                          spec)
        q = q.reshape(B, L, KV, G, d)
        k, v = k.reshape(B, L, KV, d), v.reshape(B, L, KV, d)
        ok = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0) \
            & block_visible(pos[:, :, None], pos[:, None, :], spec.block_len)
        s = _ein("btkgd,bskd->bkgts", q, k, spec) * spec.scale
        a = jax.nn.softmax(jnp.where(ok[:, None, None], s, PAGED_NEG),
                           axis=-1)
        o = _ein("bkgts,bskd->btkgd", a, v, spec)
        x = x + _mm(o.reshape(B * L, -1), theta[f"l{i}_wo"],
                    spec).reshape(B, L, D)
    with jax.named_scope("sdar/moe"):
        h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
        y, _, _, _ = moe_layer(theta, i, h2.reshape(B * L, D),
                               (seg != 0).reshape(-1), spec)
    return x + y.reshape(B, L, D)


# -- the served programs, over the block cache -----------------------------------

def cache_attend(q, pool_k, pool_v, table, length, spec: SdarSpec,
                 base=None, first=None):
    """``q [B, R, H, d]`` over each row's cached keys (every one
    visible: the cache holds whole earlier blocks; or, with ``base [B]``
    the position of the table's first row and ``first [B, R]`` each
    token row's first visible position, those from it on): the
    unnormalised online-softmax parts ``(acc [B, R, H, d], m [B, R, H],
    l)``. On a TPU with whole 128-lane heads the pool is read where it
    lies (``attention.paged_gqa_attention``); elsewhere the blocks are
    gathered (its oracle)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import attention

    B, R, H, d = q.shape
    KV, G = spec.n_kv, spec.group
    cd = jnp.dtype(spec.compute_dtype)
    # [B, R, KV, G, d] -> [B, KV, R x G, d]: a KV head's query rows
    qk = q.reshape(B, R, KV, G, d).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, R * G, d).astype(cd)
    bounds = {}
    if first is not None:
        # a KV head's query rows are (token row, head of the group)
        bounds = dict(base=base, first=jnp.repeat(first, G, axis=1))
    if jax.default_backend() == "tpu" and d % 128 == 0:
        acc, m, l = attention.paged_gqa_attention(
            qk, pool_k, pool_v, table, length, scale=spec.scale, **bounds)
    else:
        acc, m, l = attention.paged_gqa_attention_xla(
            qk, pool_k, pool_v, table, length, scale=spec.scale,
            compute_dtype=cd, **bounds)

    def back(a):
        a = a.reshape((B, KV, R, G) + a.shape[3:])
        return jnp.moveaxis(a, 1, 2).reshape((B, R, H) + a.shape[4:])

    return back(acc), back(m), back(l)


def attend(q, k_loc, v_loc, ok_loc, pool_k, pool_v, table, length,
           spec: SdarSpec, base=None, first=None):
    """Attention of ``q [B, R, H, d]`` over the cache (above) JOINED
    with the keys that are not in it: ``k_loc`` / ``v_loc`` ``[B, J,
    KV, d]`` (a query's scratch rows and the block itself) under
    ``ok_loc [B, R, J]``. ``[B, R, H x d]`` float32; zeros for a row
    that sees nothing (padding)."""
    import jax.numpy as jnp

    B, R, H, d = q.shape
    KV, G = spec.n_kv, spec.group
    acc_c, m_c, l_c = cache_attend(q, pool_k, pool_v, table, length, spec,
                                   base, first)
    s = _ein("brkgd,bjkd->brkgj", q.reshape(B, R, KV, G, d), k_loc,
             spec) * spec.scale
    ok = ok_loc[:, :, None, None, :]
    s = jnp.where(ok, s, PAGED_NEG)
    m_l = jnp.max(s, axis=-1)
    p = jnp.where(ok, jnp.exp(s - m_l[..., None]), 0.0)
    acc_l = _ein("brkgj,bjkd->brkgd", p, v_loc, spec).reshape(B, R, H, d)
    m_l, l_l = m_l.reshape(B, R, H), jnp.sum(p, -1).reshape(B, R, H)
    m = jnp.maximum(m_c, m_l)
    a, b = jnp.exp(m_c - m), jnp.exp(m_l - m)
    den = l_c * a + l_l * b
    out = (acc_c * a[..., None] + acc_l * b[..., None]) \
        / jnp.where(den > 0, den, 1.0)[..., None]
    return out.reshape(B, R, H * d)


def block_pass(theta, pool, ids, pos, valid, own_ok, scr_rows, scr_ok,
               table, length, *, spec: SdarSpec, bs: int):
    """One forward PASS of ``R`` token rows a query (``ids``, ``pos``,
    ``valid``: ``[B, R]``) through every layer: each row attends over
    its query's cached rows (``table``, ``length``), its scratch rows
    (pool rows ``scr_rows [B, J]`` under ``scr_ok [B, J]``) and the
    rows of the pass itself under ``own_ok [B, R, R]``. Returns the
    residual stream after the last layer ``[B, R, D]``, per layer the
    rows' keys and values ``[B, R, kv_width]`` (float32, as computed:
    the caller writes them) and the router's picks and gates ``[B, R,
    k]``, the experts that a valid row picked, summed over layers, and
    per layer its input ``[B, R, D]`` (what an audit holds the written
    rows against)."""
    import jax
    import jax.numpy as jnp

    B, R = ids.shape
    D, KV, d = spec.width, spec.n_kv, spec.head_dim
    J = scr_rows.shape[1]
    x = jnp.take(theta["item_emb"], ids, axis=0).astype(jnp.float32)
    ok_loc = jnp.concatenate(
        [jnp.broadcast_to(scr_ok[:, None, :], (B, R, J)), own_ok], axis=-1)
    ks, vs, picks, gates, xs = [], [], [], [], []
    touched = jnp.int32(0)
    for i in range(spec.n_layers):
        xs.append(x)
        with jax.named_scope("sdar/attn"):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            q, k, v = project(theta, i, h.reshape(B * R, D),
                              pos.reshape(-1), spec)
            k, v = k.reshape(B, R, KV, d), v.reshape(B, R, KV, d)
            pk, pv = pool["k"][i], pool["v"][i]
            # the scratch rows gathered WHOLE (``[rows, kv_width]``: the
            # pool's blocks merged, its minor dimension kept) and the
            # heads split on the gathered rows: splitting the pool's
            # minor dimension first is a copy of the whole pool
            flat = (pk.shape[0] * bs, pk.shape[-1])
            k_loc = jnp.concatenate(
                [jnp.take(pk.reshape(flat), scr_rows, axis=0,
                          mode="clip").reshape(B, J, KV, d),
                 k.astype(pk.dtype)], axis=1)
            v_loc = jnp.concatenate(
                [jnp.take(pv.reshape(flat), scr_rows, axis=0,
                          mode="clip").reshape(B, J, KV, d),
                 v.astype(pv.dtype)], axis=1)
            o = attend(q.reshape(B, R, spec.n_heads, d), k_loc, v_loc,
                       ok_loc, pk, pv, table, length, spec)
            x = x + _mm(o.reshape(B * R, -1), theta[f"l{i}_wo"],
                        spec).reshape(B, R, D)
        with jax.named_scope("sdar/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, e, w, n = moe_layer(theta, i, h2.reshape(B * R, D),
                                   valid.reshape(-1), spec)
            x = x + y.reshape(B, R, D)
        ks.append(k.reshape(B, R, -1))
        vs.append(v.reshape(B, R, -1))
        picks.append(e.reshape(B, R, -1))
        gates.append(w.reshape(B, R, -1))
        touched += n.astype(jnp.int32)
    return x, ks, vs, picks, gates, touched, xs


def _write_layer(pool, i: int, k, v, rows, bs: int):
    """Layer ``i``'s keys and values ``[T, ...]`` into its pool rows
    ``rows [T]`` (flat over blocks)."""
    out = {}
    for name, new in (("k", k), ("v", v)):
        a = pool[name][i]
        a = a.reshape(a.shape[0] * bs, -1).at[rows].set(
            new.reshape(rows.shape[0], -1).astype(a.dtype)).reshape(a.shape)
        out[name] = pool[name][:i] + (a,) + pool[name][i + 1:]
    return out


def write_rows(pool, ks, vs, rows, bs: int):
    """Every layer's keys and values of a pass into pool rows ``rows``
    (a row that is not to be kept names a row of block 0)."""
    rows = rows.reshape(-1)
    for i, (k, v) in enumerate(zip(ks, vs)):
        pool = _write_layer(pool, i, k, v, rows, bs)
    return pool


def prefill_chunk(theta, X, pool, ints, *, spec: SdarSpec, C: int, S: int,
                  bs: int, qb: int):
    """One chunk of one session's prefill: ``C`` tokens (whole blocks)
    at positions ``pos0 ..`` written to the cache and run through every
    layer against the ``S`` cached positions the block table covers
    (their own included), under the block-causal mask, ``qb`` queries
    at a time. ``ints: [3 + 2C + S / bs]`` = ``[user row (negative:
    none), pos0, valid tokens, item ids x C, cache rows x C, block
    table]``. Returns ``X`` with the final-normed hidden state of the
    chunk's last valid token in the user's row, the pool, and that
    state."""
    import jax
    import jax.numpy as jnp

    D, KV, G, d = spec.width, spec.n_kv, spec.group, spec.head_dim
    pos0, n_valid = ints[1], ints[2]
    tok = ints[3:3 + C]
    wrow = ints[3 + C:3 + 2 * C]
    table = ints[3 + 2 * C:]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < n_valid
    s_ar = jnp.arange(S, dtype=jnp.int32)
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    for i in range(spec.n_layers):
        with jax.named_scope("sdar/attn"):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            q, k, v = project(theta, i, h, pos, spec)
            pool = _write_layer(pool, i, k, v, wrow, bs)
            ks = jnp.take(pool["k"][i], table, axis=0, mode="clip").reshape(
                S, KV, d)
            vs = jnp.take(pool["v"][i], table, axis=0, mode="clip").reshape(
                S, KV, d)

            def block(args, ks=ks, vs=vs):
                q_b, pos_b = args
                ok = block_visible(pos_b[:, None], s_ar[None, :],
                                   spec.block_len) \
                    & (s_ar[None, :] < pos0 + n_valid)
                s = _ein("qkgd,skd->kgqs", q_b.reshape(qb, KV, G, d), ks,
                         spec) * spec.scale
                a = jax.nn.softmax(
                    jnp.where(ok[None, None], s, PAGED_NEG), axis=-1)
                return _ein("kgqs,skd->qkgd", a, vs, spec).reshape(qb, -1)

            o = jax.lax.map(block, (q.reshape(C // qb, qb, -1, d),
                                    pos.reshape(C // qb, qb)))
            x = x + _mm(o.reshape(C, -1), theta[f"l{i}_wo"], spec)
        with jax.named_scope("sdar/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, _, _, _ = moe_layer(theta, i, h2, valid, spec)
            x = x + y
    h_last = rms_norm(jnp.take(x, jnp.maximum(n_valid - 1, 0), axis=0),
                      theta["ln_f_g"], spec.norm_eps)
    return X.at[_user_rows(ints[0], X.shape[0])].set(
        h_last.astype(X.dtype), mode="drop"), pool, h_last


def events_width(T: int, S: int, bs: int) -> int:
    return 4 + 2 * T + S // bs


def _mark_seen(seen_bits, uid, ids):
    """``seen_bits`` with the items ``ids [B, n]`` (negative: none) set
    in the rows of users ``uid [B]`` (negative: no row); returns the
    table and the users' rows ``[B, words]``."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.mla import _new_bits

    words = seen_bits.shape[1]
    bits = jnp.take(seen_bits, uid, axis=0, mode="clip") | jax.vmap(
        lambda t, v: _new_bits(t, v, words))(jnp.maximum(ids, 0), ids >= 0)
    return seen_bits.at[_user_rows(uid, seen_bits.shape[0])].set(
        bits, mode="drop"), bits


def _commit_audit(ks, vs, picks, gates, xs, slot, dtype):
    """What a check reads of a commit pass's audited query row: per
    layer the keys and values AS THE CACHE HOLDS THEM (rounded to its
    dtype) ``[layers, R, kv_width]``, the layer's input ``x [layers, R,
    D]`` they were computed from, the router's picks and gates."""
    import jax.numpy as jnp

    held = lambda a: a[slot].astype(dtype).astype(jnp.float32)  # noqa: E731
    return {"k": jnp.stack([held(a) for a in ks]),
            "v": jnp.stack([held(a) for a in vs]),
            "x": jnp.stack([a[slot] for a in xs]),
            "picks": jnp.stack([a[slot] for a in picks]),
            "gates": jnp.stack([a[slot] for a in gates])}


def commit_events(theta, seen_bits, pool, ints, *, spec: SdarSpec, T: int,
                  S: int, bs: int, audit: bool = False):
    """New events that fill whole blocks, committed: ``B`` queries,
    each with up to ``T`` tokens (whole blocks: a session's tail and
    its new events, cut at the last block boundary) at positions
    ``len0 ..`` of its own session, run through every layer against
    the session's cache and each other under the block-causal mask;
    their keys and values are written to the session's cache rows and
    the items marked seen. ``ints: [B, 4 + 2T + S / bs]`` rows ``[user
    row (negative: padding), cached length, tokens, audit slot (row
    0's: the query row whose written rows and router picks are
    returned; negative: none), item ids x T, cache rows to write x T,
    block table]``. Returns ``seen_bits``, the pool and, compiled with
    ``audit``, :func:`_commit_audit` of the audited row (else None)."""
    import jax
    import jax.numpy as jnp

    B = ints.shape[0]
    uid, len0, n = ints[:, 0], ints[:, 1], ints[:, 2]
    tok = ints[:, 4:4 + T]
    wrow = ints[:, 4 + T:4 + 2 * T]
    table = ints[:, 4 + 2 * T:]
    pos = len0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = jnp.arange(T)[None, :] < n[:, None]
    own_ok = block_visible(pos[:, :, None], pos[:, None, :],
                           spec.block_len) & valid[:, None, :]
    none = jnp.zeros((B, 0), jnp.int32)
    seen_bits, _ = _mark_seen(seen_bits, uid, jnp.where(valid, tok, -1))
    with jax.named_scope("sdar/commit"):
        _, ks, vs, picks, gates, _, xs = block_pass(
            theta, pool, tok, pos, valid, own_ok, none, none > 0, table,
            len0, spec=spec, bs=bs)
        pool = write_rows(pool, ks, vs, wrow, bs)
    if not audit:
        return seen_bits, pool, None
    slot = jnp.maximum(ints[0, 3], 0)
    return seen_bits, pool, _commit_audit(ks, vs, picks, gates, xs, slot,
                                          pool["k"][0].dtype)


def unmask_step(conf, tok, masked, quota, *, spec: SdarSpec):
    """Which masked positions a pass unmasks: ``conf`` / ``tok`` /
    ``masked``: ``[B, R]``, ``quota [B]`` (the static rule's count a
    pass). Candidates go most confident first (ties: the earlier
    position); ``low_confidence_static`` takes the first ``quota``,
    ``low_confidence_dynamic`` every one at ``threshold`` or above and
    always the first. A candidate whose token another position took IN
    THIS PASS is left masked (the next pass sees that token in the
    slate): a slate never repeats an item. ``[B, R]`` bool."""
    import jax.numpy as jnp

    B, R = conf.shape
    c = jnp.where(masked, conf, -1.0)
    order = jnp.argsort(-c, axis=1, stable=True)
    rows = jnp.arange(B)
    accept = jnp.zeros((B, R), bool)
    took = jnp.full((B, R), -1, jnp.int32)
    count = jnp.zeros((B,), jnp.int32)
    for r in range(R):
        j = order[:, r]
        cj, tj, mj = c[rows, j], tok[rows, j], masked[rows, j]
        if spec.remasking == "low_confidence_static":
            want = count < quota
        else:
            want = (cj >= spec.threshold) | (r == 0)
        ok = mj & want & ~jnp.any(took == tj[:, None], axis=1)
        accept = accept.at[rows, j].set(ok)
        took = took.at[:, r].set(jnp.where(ok, tj, -1))
        count += ok
    return accept


def round_width(R: int, J: int, S: int, bs: int) -> int:
    return 8 + 2 * R + 2 * J + NEW_EVENTS + S // bs


def slate_round(theta, seen_bits, pool, Y, ints, *, spec: SdarSpec, S: int,
                J: int, bs: int, n_items: int, mode: str,
                audit: bool = False):
    """One ROUND of the slate lane: ``B`` query rows, each decoding ONE
    block of its slate. ``ints: [B, 8 + 2R + 2J + NEW_EVENTS + S /
    bs]`` int32 rows ``[user row (negative: padding), cached length, the
    block's first position, its rows (the last block of a slate is cut),
    of those the leading FIXED ones (a session's tail events, in the
    slate's first block), scratch rows held, a spare, audit slot (row
    0's; negative: none), the block's ids x R (fixed ones; the rest are
    masks), the slate so far x J (-1: none), new events to mark seen x
    NEW_EVENTS (-1: none), the query's scratch rows x J (pool rows, in
    position order), the pool rows the finished block is written to x
    R, block table]``.

    The passes are a device loop: a pass runs the block's ``R`` rows
    through every layer against the cache, the query's scratch rows and
    the block itself (bidirectional inside), scores the output table,
    masks what the user has seen, the slate so far and the block's own
    tokens, takes each masked position's argmax and its softmax
    probability (the confidence) and unmasks by the rule
    (:func:`unmask_step`), until no row holds a mask. Then one COMMIT
    pass over the finished block writes its keys and values behind the
    query's scratch rows. Returns ``(packed [B, 3R + 1 + COUNTERS]
    int32: the block's tokens, their confidences' bits, the pass that
    unmasked each (-1: fixed or no row), the row's passes, then the
    dispatch's passes, the experts valid rows picked and the cached and
    scratch rows read, each summed over its passes),
    seen_bits, pool, audit | None)``; the audit, of row ``audit
    slot``: per pass ``ids`` / ``masked`` / ``picked`` ``[R, R]``,
    ``logits [R passes, R, items]`` (before any mask), ``routed [R
    passes, layers, R, k]`` (the router's picks), and of the commit
    pass :func:`_commit_audit`'s ``k`` / ``v`` / ``x`` / ``picks`` /
    ``gates``."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.als_pallas import unpack_seen_bits
    from predictionio_tpu.ops.serving import _score_einsum

    B, R = ints.shape[0], spec.block_len
    uid, len0, pos0, n_rows, n_fixed, scr_n = (ints[:, c] for c in range(6))
    slot = jnp.maximum(ints[0, 7], 0)
    c0 = 8
    ids0 = ints[:, c0:c0 + R]
    taken = ints[:, c0 + R:c0 + R + J]
    c1 = c0 + R + J
    new = ints[:, c1:c1 + NEW_EVENTS]
    scr_rows = ints[:, c1 + NEW_EVENTS:c1 + NEW_EVENTS + J]
    wrow = ints[:, c1 + NEW_EVENTS + J:c1 + NEW_EVENTS + J + R]
    table = ints[:, c1 + NEW_EVENTS + J + R:]
    M = Y.shape[0]
    mask_id = spec.mask_row(theta["item_emb"].shape[0])
    r_ar = jnp.arange(R, dtype=jnp.int32)
    pos = pos0[:, None] + r_ar[None, :]
    valid = r_ar[None, :] < n_rows[:, None]
    masked0 = valid & (r_ar[None, :] >= n_fixed[:, None])
    ids_start = jnp.where(masked0, mask_id, jnp.where(valid, ids0, mask_id))
    own_ok = jnp.broadcast_to(valid[:, None, :], (B, R, R))
    scr_ok = jnp.arange(J)[None, :] < scr_n[:, None]
    quota = -(-jnp.sum(masked0, axis=1) // spec.steps)
    with jax.named_scope("sdar/unmask"):
        seen_bits, bits = _mark_seen(seen_bits, uid, new)
        col = jnp.arange(M, dtype=jnp.int32)
        barred = jax.vmap(lambda r: unpack_seen_bits(r, M))(bits) \
            | (col >= n_items)[None, :] | (col == mask_id)[None, :]
        rows_b = jnp.arange(B)[:, None]
        barred = barred.at[rows_b, jnp.where(taken >= 0, taken, M)].set(
            True, mode="drop")
    pass_kw = dict(spec=spec, bs=bs)

    def one_pass(state):
        it, ids, masked, conf, when, touched, aud = state
        x, _, _, picks, _, n, _ = block_pass(
            theta, pool, ids, pos, valid, own_ok, scr_rows, scr_ok, table,
            len0, **pass_kw)
        with jax.named_scope("sdar/head"):
            hq = rms_norm(x, theta["ln_f_g"], spec.norm_eps).reshape(
                B * R, -1)
            logits = _score_einsum("mr,br->bm", Y, hq.astype(Y.dtype),
                                   mode=mode).astype(jnp.float32).reshape(
                                       B, R, M)
        with jax.named_scope("sdar/unmask"):
            own = jnp.where(valid & ~masked, ids, M)
            bar = barred.at[rows_b, own].set(True, mode="drop")
            z = jnp.where(bar[:, None, :], -jnp.inf, logits)
            tok = jnp.argmax(z, axis=-1).astype(jnp.int32)
            top = jnp.max(z, axis=-1)
            c = 1.0 / jnp.sum(jnp.exp(z - top[..., None]), axis=-1)
            accept = unmask_step(c, tok, masked, quota, spec=spec)
            new_ids = jnp.where(accept, tok, ids)
            if aud is not None:
                aud = {
                    "ids": aud["ids"].at[it].set(ids[slot]),
                    "masked": aud["masked"].at[it].set(masked[slot]),
                    "picked": aud["picked"].at[it].set(accept[slot]),
                    "routed": aud["routed"].at[it].set(
                        jnp.stack([e[slot] for e in picks])),
                    "logits": jax.lax.dynamic_update_slice(
                        aud["logits"], logits[slot][None], (it, 0, 0))}
        return (it + 1, new_ids, masked & ~accept,
                jnp.where(accept, c, conf), jnp.where(accept, it, when),
                touched + n, aud)

    aud0 = None
    if audit:
        aud0 = {"ids": jnp.zeros((R, R), jnp.int32),
                "masked": jnp.zeros((R, R), bool),
                "picked": jnp.zeros((R, R), bool),
                "routed": jnp.zeros((R, spec.n_layers, R, spec.per_token),
                                    jnp.int32),
                "logits": jnp.zeros((R, R, M), jnp.float32)}
    state = (jnp.int32(0), ids_start, masked0,
             jnp.where(valid & ~masked0, 1.0, 0.0).astype(jnp.float32),
             jnp.full((B, R), -1, jnp.int32), jnp.int32(0), aud0)
    it, ids, _, conf, when, touched, aud = jax.lax.while_loop(
        lambda s: jnp.any(s[2]) & (s[0] < R), one_pass, state)
    with jax.named_scope("sdar/commit"):
        _, ks, vs, picks, gates, n, xs = block_pass(
            theta, pool, ids, pos, valid, own_ok, scr_rows, scr_ok, table,
            len0, **pass_kw)
        # a row that is no token writes to block 0
        pool = write_rows(pool, ks, vs,
                          jnp.where(valid, wrow, r_ar[None, :]), bs)
    row_passes = jnp.max(when, axis=1) + 2    # its last pass, and the commit
    live = uid >= 0
    read = jnp.sum(jnp.where(live, len0 + scr_n, 0)) * (it + 1)
    counts = jnp.stack([it + 1, touched + n, read])
    packed = jnp.concatenate(
        [ids, jax.lax.bitcast_convert_type(conf, jnp.int32), when,
         jnp.where(live, row_passes, 0)[:, None],
         jnp.broadcast_to(counts, (B, COUNTERS))], axis=-1)
    if aud is not None:
        aud = dict(aud, passes=it, **_commit_audit(
            ks, vs, picks, gates, xs, slot, pool["k"][0].dtype))
    return packed, seen_bits, pool, aud
