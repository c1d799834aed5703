"""SmallThinker-21BA3B's block (``model_name: smallthinker_21b_instruct``)
for the sequence lane: layers of TWO KINDS in a published pattern
(``sliding_window_layout``, equal to ``rope_layout``: one GLOBAL layer
in four, without positions (NoPE), attending every earlier position;
three WINDOW layers, rotated (half-split RoPE), attending the
``sliding_window_size`` newest positions, their own included), grouped
-query attention (28 heads on 4 key/value heads: groups of 7, no QK
norm, no bias), and 64 ReGLU experts (``relu(gate) * up``) of which 6 a
token, picked by a router that reads the ATTENTION's input
(``input_layernorm(x)``), weighted by a softmax over the picked logits.

Per token the cache holds, for every layer, the key and the value rows
(``num_key_value_heads x head_dim`` values each). A window layer never
reads a row more than ``window - 1`` positions behind a query's, so the
session lane keeps one block table a LAYER KIND (``ops/sessions.py``)
and a window layer's table starts at the oldest block the session
still holds (its ``base``).

This file holds the device programs: the full forward
(:func:`smallthinker_layer`: the tests' encoder) and the two served
ones, :func:`prefill_chunk` and :func:`extend_step`.
``ops/sessions.py::SmallThinkerBackbone`` drives them;
``ops/smallthinker_reference.py`` is the plain float32 reference of the
same equations. The projections, the rotation, the cache writes, the
join of cached and new keys and the experts' call are
``ops/sdar.py``'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.ops import sdar
from predictionio_tpu.ops.attention import PAGED_NEG
from predictionio_tpu.ops.mla import _ein, _mm, _user_rows, rms_norm

ACTIVATION = "relu"     # ReGLU experts
KIND_NAMES = ("global", "window")   # by the layout's value of a layer


@dataclasses.dataclass(frozen=True)
class SwaSpec:
    """What of ``SeqRecParams`` shapes the ``smallthinker`` programs."""

    n_layers: int
    width: int
    n_heads: int
    n_kv: int
    head_dim: int
    expert_width: int
    n_experts: int
    per_token: int
    norm_eps: float
    rope_theta: float
    compute_dtype: str
    window: int
    pattern: Tuple[int, ...]    # a layer: 1 window + RoPE, 0 global NoPE
    max_positions: int
    # a softmax over the picked logits IS the full softmax renormalised
    # over the picks (``moe.route(renorm=True)``)
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
    def kinds(self) -> Tuple[Tuple[str, Tuple[int, ...], Optional[int]], ...]:
        """``(name, layers, positions kept)`` of the layer kinds the
        pattern holds, global first: a global layer keeps every
        position (None), a window layer the ``window`` newest."""
        out = []
        for g, keep in ((0, None), (1, self.window)):
            layers = tuple(i for i, p in enumerate(self.pattern) if p == g)
            if layers:
                out.append((KIND_NAMES[g], layers, keep))
        return tuple(out)

    def kind_of(self, i: int) -> int:
        """Layer ``i``'s index into :attr:`kinds`."""
        names = [k[0] for k in self.kinds]
        return names.index(KIND_NAMES[self.pattern[i]])


def swa_spec(params) -> SwaSpec:
    """``SeqRecParams(block="smallthinker", ...)`` -> :class:`SwaSpec`."""
    need = ("n_kv_heads", "head_dim", "n_experts", "expert_width",
            "experts_per_token", "sliding_window_size")
    zero = [k for k in need if int(getattr(params, k)) <= 0]
    if zero:
        raise ValueError(f"the smallthinker block needs {', '.join(zero)}")
    if (params.norm, params.positions, bool(params.tied),
            bool(params.norm_topk_prob)) != ("rmsnorm", "rope", False, True):
        raise ValueError(
            "the smallthinker block takes norm rmsnorm, positions rope "
            "(on its window layers), untied tables (tied false) and "
            "norm_topk_prob true, as SmallThinker publishes it")
    L = int(params.n_layers)
    pattern = tuple(int(g) for g in params.sliding_window_layout)[:L]
    if len(pattern) < L or set(pattern) - {0, 1}:
        raise ValueError(
            f"sliding_window_layout names {len(pattern)} of {L} layers' "
            "kinds (0: global NoPE, 1: rotary window)")
    H, KV = int(params.n_heads), int(params.n_kv_heads)
    if H % KV or int(params.head_dim) % 2:
        raise ValueError(f"{H} query heads do not share {KV} key/value "
                         "heads evenly, or head_dim is odd")
    if int(params.experts_per_token) > int(params.n_experts):
        raise ValueError("experts_per_token over n_experts")
    return SwaSpec(
        L, int(params.rank), H, KV, int(params.head_dim),
        int(params.expert_width), int(params.n_experts),
        int(params.experts_per_token), float(params.norm_eps),
        float(params.rope_theta), str(params.compute_dtype),
        int(params.sliding_window_size), pattern, int(params.max_seq_len))


# -- parameters ----------------------------------------------------------------

is_low = sdar.is_low


def theta_shapes(V: int, spec: SwaSpec
                 ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter in drawing order, in
    ``ops/seqrec.py::_theta_shapes``'s form: SDAR's without the QK
    norms' gains."""
    return [s for s in sdar.theta_shapes(V, spec)
            if not s[0].endswith(("_qn_g", "_kn_g"))]


def draw_serving_theta(V: int, params, skip: Tuple[str, ...] = ()):
    """The seeded parameters ``init_theta_device`` draws (same keys,
    same order), drawn ON THE DEVICE into the dtype each is served in,
    one jitted call a layer (``ops/mla.py::draw_shapes``)."""
    from predictionio_tpu.ops import mla

    spec = swa_spec(params)
    return mla.draw_shapes(theta_shapes(V, spec), int(params.seed),
                           spec.n_layers, spec.compute_dtype, is_low, skip)


serving_theta = sdar.serving_theta


# -- pieces --------------------------------------------------------------------

def visible(pos_q, pos_k, window: Optional[int]):
    """Key at ``pos_k`` is visible to the query at ``pos_q``: not after
    it and, in a window layer, fewer than ``window`` positions behind
    (the query's own position and ``window - 1`` before it)."""
    ok = pos_k <= pos_q
    return ok if window is None else ok & (pos_q - pos_k < window)


def _window_of(spec: SwaSpec, i: int) -> Optional[int]:
    return spec.window if spec.pattern[i] else None


def _scope(spec: SwaSpec, i: int) -> str:
    return "swa/attn/" + KIND_NAMES[spec.pattern[i]]


def experts(theta, i: int, h2, h, valid, spec: SwaSpec):
    """The expert layer on ``h2`` (``post_attention_layernorm(x)``),
    routed from ``h`` (the attention's input)."""
    return sdar.moe_layer(theta, i, h2, valid, spec, router_input=h,
                          activation=ACTIVATION)


# -- the full forward pass: the tests' encoder ------------------------------------

def smallthinker_layer(theta, i: int, x, seg, pos, spec: SwaSpec):
    """One layer over whole rows ``x: [B, L, D]``: position ``t`` sees
    the positions of its own segment that :func:`visible` allows its
    kind of layer (dense masked attention, the key/value heads shared
    by their query groups)."""
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    KV, G, d = spec.n_kv, spec.group, spec.head_dim
    with jax.named_scope(_scope(spec, i)):
        h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
        q, k, v = sdar.project(theta, i, h.reshape(B * L, D),
                               pos.reshape(-1), spec,
                               rotate=bool(spec.pattern[i]))
        q = q.reshape(B, L, KV, G, d)
        k, v = k.reshape(B, L, KV, d), v.reshape(B, L, KV, d)
        ok = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0) \
            & visible(pos[:, :, None], pos[:, None, :], _window_of(spec, i))
        s = _ein("btkgd,bskd->bkgts", q, k, spec) * spec.scale
        a = jax.nn.softmax(jnp.where(ok[:, None, None], s, PAGED_NEG),
                           axis=-1)
        o = _ein("bkgts,bskd->btkgd", a, v, spec)
        x = x + _mm(o.reshape(B * L, -1), theta[f"l{i}_wo"],
                    spec).reshape(B, L, D)
    with jax.named_scope("swa/moe"):
        h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
        y, _, _, _ = experts(theta, i, h2.reshape(B * L, D),
                             h.reshape(B * L, D), (seg != 0).reshape(-1),
                             spec)
    return x + y.reshape(B, L, D)


# -- the served programs, over the block cache -----------------------------------

def _kind_ints(ints, layout, kind: int, T: int):
    """A kind's part of a row (or rows) of ``ints``: ``(cache rows to
    write x T, the position of its table's first row | None, its block
    table)``; ``layout``: ``sessions.kind_layout``."""
    w, b, t, nb = layout[kind]
    return (ints[..., w:w + T], None if b < 0 else ints[..., b],
            ints[..., t:t + nb])


def chunk_attend(q, pool_k, pool_v, table, base, pos, pos0, n_valid,
                 window: Optional[int], spec, qb: int):
    """A prefill chunk's queries ``q [C, H, d]`` at positions ``pos``
    over the cached rows their layer's block ``table`` covers (the
    chunk's own rows are written already; ``base``: the position of the
    table's first row, None: 0), gathered once and scored ``qb``
    queries at a time under :func:`visible` and the chunk's end
    ``pos0 + n_valid``. ``[C / qb, qb, H x d]`` float32."""
    import jax
    import jax.numpy as jnp

    KV, G, d = spec.n_kv, spec.group, spec.head_dim
    C, bs = q.shape[0], pool_k.shape[1]
    rows = table.shape[0] * bs
    ks = jnp.take(pool_k, table, axis=0, mode="clip").reshape(rows, KV, d)
    vs = jnp.take(pool_v, table, axis=0, mode="clip").reshape(rows, KV, d)
    at = jnp.arange(rows, dtype=jnp.int32) + (0 if base is None else base)

    def block(args):
        q_b, pos_b = args
        ok = visible(pos_b[:, None], at[None, :], window) \
            & (at[None, :] < pos0 + n_valid)
        s = _ein("qkgd,skd->kgqs", q_b.reshape(qb, KV, G, d), ks,
                 spec) * spec.scale
        a = jax.nn.softmax(
            jnp.where(ok[None, None], s, PAGED_NEG), axis=-1)
        return _ein("kgqs,skd->qkgd", a, vs, spec).reshape(qb, -1)

    return jax.lax.map(block, (q.reshape(C // qb, qb, -1, d),
                               pos.reshape(C // qb, qb)))


def prefill_chunk(theta, X, pool, ints, *, spec: SwaSpec, C: int, S: int,
                  bs: int, qb: int, layout: Tuple):
    """One chunk of one session's prefill: ``C`` tokens at positions
    ``pos0 ..`` written to the cache and run through every layer
    against the cached positions its kind's block table covers (their
    own included: a window layer's table holds the chunk and the
    ``window - 1`` positions before it), ``qb`` queries at a time.
    ``ints`` = ``[user row (negative: none), pos0, valid tokens, item
    ids x C, then a layer kind at a time: cache rows x C, (a window
    kind: the position of its table's first row,) block table]``.
    Returns ``X`` with the final-normed hidden state of the chunk's
    last valid token in the user's row, the pool, and that state."""
    import jax
    import jax.numpy as jnp

    pos0, n_valid = ints[1], ints[2]
    tok = ints[3:3 + C]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < n_valid
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    for i in range(spec.n_layers):
        wrow, base, table = _kind_ints(ints, layout, spec.kind_of(i), C)
        window = _window_of(spec, i)
        with jax.named_scope(_scope(spec, i)):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            q, k, v = sdar.project(theta, i, h, pos, spec,
                                   rotate=bool(spec.pattern[i]))
            pool = sdar._write_layer(pool, i, k, v, wrow, bs)
            o = chunk_attend(q, pool["k"][i], pool["v"][i], table, base,
                             pos, pos0, n_valid, window, spec, qb)
            x = x + _mm(o.reshape(C, -1), theta[f"l{i}_wo"], spec)
        with jax.named_scope("swa/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, _, _, _ = experts(theta, i, h2, h, valid, spec)
            x = x + y
    h_last = rms_norm(jnp.take(x, jnp.maximum(n_valid - 1, 0), axis=0),
                      theta["ln_f_g"], spec.norm_eps)
    return X.at[_user_rows(ints[0], X.shape[0])].set(
        h_last.astype(X.dtype), mode="drop"), pool, h_last


def extend_step(theta, X, seen_bits, pool, Y, ints, *, spec: SwaSpec,
                kb: int, T: int, S: int, bs: int, n_items: int, mode: str,
                layout: Tuple, audit: bool = False):
    """One dispatch of the session lane: ``B`` queries, each appending
    up to ``T`` events to its own session and asking for its top
    ``kb``. ``ints: [B, ...]`` int32 rows ``[user row (negative: none,
    nothing is written for it), cached length, new events, item ids x
    T, then a layer kind at a time: cache rows to write x T, (a window
    kind: the position of its table's first row,) the session's block
    table of that kind]``. Every layer's new rows attend over the
    session's cached rows (the paged kernel on a TPU: a window layer's
    rows each from their own first visible position on, its table
    starting at the oldest block the session still holds) joined with
    the new rows themselves, then their keys and values are written.
    Returns the packed top-k, the new ``X``, ``seen_bits``, the pool
    and, compiled with ``audit``, what a check compares (else None):
    every item's ``scores`` ``[B, items]`` and, for each row's last new
    event, ``layers`` ``[n_layers, B, D]`` (the residual stream after
    every layer), ``k`` / ``v`` ``[n_layers, B, kv_width]`` (the cache
    rows written for it, as the cache holds them), ``picks`` / ``gates``
    ``[n_layers, B, k]`` and ``h`` ``[n_layers, B, D]`` (the router's
    picks, their weights and its input: the attention's), ``first``
    ``[n_layers, B]`` (the first position the layer read for it).
    Four float32 counters ride as int32 bits behind the packed
    columns: cache rows the global and the window layers had to read
    (a query's visible rows, its new ones with them, summed over the
    kind's layers), the experts a valid token picked summed over
    layers, and a spare."""
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
    x = jnp.take(theta["item_emb"], tok, axis=0).astype(jnp.float32)
    kept: Dict[str, list] = {k: [] for k in (
        "layers", "k", "v", "picks", "gates", "h", "first")}
    read = [jnp.float32(0), jnp.float32(0)]
    touched = jnp.float32(0)
    take_last = lambda a: jnp.take_along_axis(  # noqa: E731
        a, last.reshape((B, 1) + (1,) * (a.ndim - 2)), axis=1)[:, 0]
    for i in range(spec.n_layers):
        wrow, base, table = _kind_ints(ints, layout, spec.kind_of(i), T)
        window = _window_of(spec, i)
        with jax.named_scope(_scope(spec, i)):
            h = rms_norm(x, theta[f"l{i}_ln1_g"], spec.norm_eps)
            q, k, v = sdar.project(theta, i, h.reshape(B * T, D),
                                   tpos.reshape(-1), spec,
                                   rotate=bool(spec.pattern[i]))
            k, v = k.reshape(B, T, KV, d), v.reshape(B, T, KV, d)
            pk, pv = pool["k"][i], pool["v"][i]
            own_ok = visible(tpos[:, :, None], tpos[:, None, :], window) \
                & tvalid[:, None, :]
            first = None if window is None \
                else jnp.maximum(tpos - window + 1, 0)
            o = sdar.attend(q.reshape(B, T, H, d), k.astype(pk.dtype),
                            v.astype(pv.dtype), own_ok, pk, pv, table, len0,
                            spec, base=base, first=first)
            x = x + _mm(o.reshape(B * T, -1), theta[f"l{i}_wo"],
                        spec).reshape(B, T, D)
            pool = sdar._write_layer(pool, i, k, v, wrow.reshape(-1), bs)
        with jax.named_scope("swa/moe"):
            h2 = rms_norm(x, theta[f"l{i}_ln2_g"], spec.norm_eps)
            y, e, w, n = experts(theta, i, h2.reshape(B * T, D),
                                 h.reshape(B * T, D), tvalid.reshape(-1),
                                 spec)
            x = x + y.reshape(B, T, D)
        seen_rows = len0 + n_new if window is None \
            else jnp.minimum(len0, window - 1) + n_new
        read[spec.pattern[i]] += jnp.sum(jnp.where(live, seen_rows, 0))
        touched += n
        if audit:
            held = lambda a, p=pk: take_last(  # noqa: E731
                a.reshape(B, T, -1)).astype(p.dtype).astype(jnp.float32)
            kept["layers"].append(take_last(x))
            kept["k"].append(held(k))
            kept["v"].append(held(v))
            kept["picks"].append(take_last(e.reshape(B, T, -1)))
            kept["gates"].append(take_last(w.reshape(B, T, -1)))
            kept["h"].append(take_last(h))
            kept["first"].append(
                jnp.zeros((B,), jnp.int32) if first is None
                else take_last(first))
    with jax.named_scope("swa/head"):
        counts = jnp.stack([read[0], read[1], touched, jnp.float32(0)])
        packed, X, seen_bits, scores = score_head(
            theta, X, seen_bits, Y, jnp.take_along_axis(
                x, last[:, None, None], axis=1)[:, 0], uid, n_new, tok,
            tvalid, counts, eps=spec.norm_eps, kb=kb, n_items=n_items,
            mode=mode, mask_seen=True)
    if not audit:
        return packed, X, seen_bits, pool, None
    return packed, X, seen_bits, pool, dict(
        {k: jnp.stack(v) for k, v in kept.items()}, scores=scores)
