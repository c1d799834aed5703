"""Plain float32 reference of GLM-5's block (``model_type:
glm_moe_dsa``) as the sequence lane serves it: the full forward pass
over ONE user's whole history. ``jax.numpy`` only, every product at
``jax.default_matmul_precision("highest")``; no cache, no kernels, no
dispatch: keys and values are EXPANDED from the latents, attention
materialises its masked scores, and every held expert runs on the
tokens that picked it (found by a top-k over its gate column, never by
a dispatch plan: half of a block's rows an expert, and a block in
which more picked one runs every token through every expert). The work is cut into blocks of queries
that are jitted with fixed shapes, because op-by-op execution compiles
every distinct shape of every operation (1,023 compiles, 135 s, for
4,096 events on the chip: PERF.md, PR 30).
``benchmark/harness/oracle_glm5.py`` is a copy of this file: the
benchmark's session cell compares the served lane with it on the chip.

The model, from the published ``config.json`` of GLM-5 and the
family's public code (DeepSeek-V3.2's latent attention and indexer).
Pre-norm residual layer, RMSNorm eps 1e-5, no bias. For token ``t``
with input ``x_t``:

- latent attention: ``cq = RMSNorm(W_qa x)``; ``q = W_qb cq``, 64
  heads of 256, each ``[q_nope 192 | q_rope 64]``; ``[ckv ; kr] = W_kva
  x`` (512 + 64), ``ckv = RMSNorm(ckv)``; RoPE (theta 1e6, interleaved
  pairs) on ``q_rope`` and on ``kr``, which all heads share;
  ``[k_nope ; v] = W_kvb ckv`` per head (192 + 256); softmax over the
  selected positions of ``(q_nope . k_nope + q_rope . kr) / sqrt(256)``;
  the heads' outputs through ``W_o``;
- indexer: ``qi_j = W_iq cq`` (32 heads of 128), ``ki = LayerNorm(W_ik
  x)`` (128), RoPE on the first 64 dimensions of both, ``w = W_iw x /
  sqrt(32 * 128)``; ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``;
  position ``t`` attends the 2,048 positions ``s <= t`` with the
  largest ``I`` (all of them while ``t < 2,048``);
- feed-forward: the leading layer(s) ``W_down(silu(W_gate h) * W_up
  h)``; the others ``s = sigmoid(W_r h)``, the 8 experts with the
  largest ``s + b``, weights ``2.5 s_i / sum_chosen s``, plus the
  shared expert once;
- head: final RMSNorm, scores against the output table.

Departures from the published model:

- item ids stand for tokens; the tables hold the catalog's rows;
- THIS CHIP'S SHARE: the router keeps its 256 outputs and its 8 a
  token; of the experts it picks, only those this chip holds
  (``held`` from ``first``) add to the output, as on one of the 16
  chips that share a layer in the deployment (what the absent experts
  would add is left out here and in the program alike);
- the multi-token-prediction layer is not run (the family's published
  inference code drops its weights at load);
- ASSUMED, because ``config.json`` names only the sizes: the indexer
  key's norm is a LayerNorm with bias (eps as the RMSNorm's), ``w`` is
  scaled by ``1 / sqrt(heads * width)``, the rotated dimensions of the
  indexer are its first 64, ``head_dim: 64`` is read as the rotated
  part's width and the softmax scale uses ``qk_head_dim`` 256; the
  published system's Hadamard rotation and float8 rounding of the
  indexer's operands are not modelled (an orthogonal rotation changes
  no dot product); the router's bias is seeded non-zero.

Controls and planted faults (what the benchmark's comparison must
catch; the faults are DATA of the jitted blocks, ``knobs_of``, so one
compiled program serves the sound pass and every control):
``cache_dtype`` rounds the three cached quantities (``ckv``, ``kr``,
``ki``) through a lower dtype; ``router_dtype`` (bfloat16) rounds the
router product's operands; ``fault`` is one of ``FAULTS``:
``dropped_expert`` (held expert ``fault_expert`` adds nothing),
``index_skips_last_block`` (the indexer never scores the
``fault_block`` positions before a query), ``index_swaps_tenth`` (a
tenth of the kept keys give way to keys left out, whatever their
scores), ``router_ignores_bias`` (the choice by the score alone) and
``stale_row`` (every eighth position's cache rows are the position
before's: a write that lands a row late).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
AUDITED = 32      # audited positions a block of queries holds, or multiples
CUTS = ("index_low", "index_out", "index_regret", "router_low",
        "router_out")
FAULTS = ("dropped_expert", "index_skips_last_block", "index_swaps_tenth",
          "router_ignores_bias", "stale_row")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def _r(x, dtype):
    """``x`` rounded through ``dtype``. bfloat16 by an operation the
    compiler may not elide: with excess precision allowed the chip's
    compiler drops a float32 -> bfloat16 -> float32 pair of casts (the
    bf16 router control read exactly the sound pass, my chip run, PR
    30)."""
    if dtype is None:
        return x
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(jnp.float32)


def rms_norm(x, g, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def rope(x, pos, theta: float):
    """Interleaved rotary positions: the pair ``(x[2i], x[2i + 1])`` of
    ``x: [L, ..., d]`` turns by ``pos[L] * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _gated(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def _cut_readings(score, chosen, k: int):
    """How far a GIVEN cut (``chosen``: bool over candidates, -inf
    scores never eligible) is from the reference's own top ``k`` of
    ``score``, each as a share of the eligible scores' standard
    deviation: ``low`` (the reference's k-th score less the lowest
    chosen one), ``out`` (the highest one left out less the k-th) and
    ``regret`` (what the reference's own top ``k`` sum to, less what
    the chosen sum to, a chosen candidate). All three are 0 for any
    top-``k`` set, whichever way round a tie is taken. ``low`` and
    ``out`` are the WORST candidate's and ``regret`` the mean's: over
    thousands of candidates a handful of outliers decide the first two
    and barely move the third."""
    fin = score > -jnp.inf
    n = jnp.sum(fin)
    kk = min(k, score.shape[-1])
    top = jax.lax.top_k(score, kk)[0]
    kth = jnp.where(n <= k, -jnp.inf, top[-1])
    mean = jnp.sum(jnp.where(fin, score, 0.0)) / jnp.maximum(n, 1)
    spread = jnp.sqrt(jnp.sum(jnp.where(fin, (score - mean) ** 2, 0.0))
                      / jnp.maximum(n, 1)) + 1e-30
    low = jnp.min(jnp.where(chosen, score, jnp.inf))
    out = jnp.max(jnp.where(chosen | ~fin, -jnp.inf, score))
    want = jnp.minimum(n, k)
    full = jnp.sum(chosen & fin) == want
    best = jnp.sum(jnp.where(top > -jnp.inf, top, 0.0))
    got = jnp.sum(jnp.where(chosen & fin, score, 0.0))
    return jnp.stack([
        jnp.where(full, jnp.maximum(kth - low, 0.0) / spread, jnp.inf),
        jnp.where(n <= k, 0.0, jnp.maximum(out - kth, 0.0) / spread),
        jnp.where(full, jnp.maximum(best - got, 0.0)
                  / (jnp.maximum(want, 1) * spread), jnp.inf)])


def _noise(t, s):
    """A fixed pseudo-random number in [0, 1) a (query, key) pair."""
    h = t.astype(jnp.uint32) * jnp.uint32(2654435761) \
        + s.astype(jnp.uint32) * jnp.uint32(40503)
    h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return (h & jnp.uint32(0xFFFFFF)).astype(jnp.float32) / float(1 << 24)


def knobs_of(fault: Optional[str] = None, router_dtype=None,
             fault_block: int = 0, fault_expert: int = 0) -> Dict[str, Any]:
    """The planted faults as DATA of the jitted blocks (one compiled
    program serves the sound pass and every control)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if router_dtype is not None and jnp.dtype(router_dtype) != jnp.bfloat16:
        raise ValueError("router_dtype: None or bfloat16")
    return {
        "skip": np.int32(fault_block
                         if fault == "index_skips_last_block" else 0),
        "swap": np.bool_(fault == "index_swaps_tenth"),
        "drop": np.int32(fault_expert if fault == "dropped_expert" else -1),
        "no_bias": np.bool_(fault == "router_ignores_bias"),
        "round_router": np.bool_(router_dtype is not None)}


_STATIC = ("cfg_key", "dense", "hg", "cap")


def _cfg(cfg_key):
    return dict(cfg_key)


@functools.partial(jax.jit, static_argnames=("cfg_key", "cache_dtype"))
def _keys_block(w, x, pos, *, cfg_key, cache_dtype):
    """A block of positions' cached quantities ``(ckv, kr, ki)``."""
    c = _cfg(cfg_key)
    R, dr, eps, rt = c["kv_rank"], c["d_rope"], c["norm_eps"], c["rope_theta"]
    h = rms_norm(x, w["ln1_g"], eps)
    kva = _mm(h, w["wkv_a"])
    ckv = rms_norm(kva[:, :R], w["kva_g"], eps)
    kr = rope(kva[:, R:], pos, rt)
    k_ = layer_norm(_mm(h, w["wik"]), w["ik_g"], w["ik_b"], eps)
    ki = jnp.concatenate([rope(k_[:, :dr], pos, rt), k_[:, dr:]], axis=-1)
    return tuple(_r(v, cache_dtype) for v in (ckv, kr, ki))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _query_block(w, x, pos, ckv, kr, ki, a_rows, a_given, a_sel, a_pick,
                 knobs, *, cfg_key, dense, hg, cap):
    """One layer for a block of ``n`` queries against the ``b`` cached
    positions it may see (``pos[s] <= pos[t]``; the caller passes the
    keys up to the block's own end, rounded up). ``a_rows`` (``[A]``
    rows of this block, -1: none) are AUDITED: what the layer did for
    them is returned; those with ``a_given`` take the GIVEN cuts
    ``a_sel`` / ``a_pick`` first. Returns the block's output, the
    audited rows' ``{"selected" [A, K] positions attended (-1: none),
    "picks" [A, k], "gates" [A, k] the picks' weights, "h2" [A, D] the
    router's input, "cuts" [A, 5] (``CUTS``; 0 where nothing was
    given)}``, and by how many tokens the busiest held expert's picks
    passed ``cap``, the rows an expert is run on (0: none was left
    out)."""
    c = _cfg(cfg_key)
    H, dn, dr, dv = c["n_heads"], c["d_nope"], c["d_rope"], c["d_v"]
    R, K, eps, rt = c["kv_rank"], c["idx_topk"], c["norm_eps"], \
        c["rope_theta"]
    J, di = c["idx_heads"], c["idx_dim"]
    n, b = x.shape[0], ckv.shape[0]
    A = a_rows.shape[0]
    on = a_rows >= 0
    rows = jnp.maximum(a_rows, 0)             # to read
    put = jnp.where(on & a_given, a_rows, n)  # to write (n: dropped)
    key_pos = jnp.arange(b, dtype=jnp.int32)
    h = rms_norm(x, w["ln1_g"], eps)
    cq = rms_norm(_mm(h, w["wq_a"]), w["qa_g"], eps)
    q = _mm(cq, w["wq_b"]).reshape(n, H, dn + dr)
    q_rope = rope(q[..., dn:], pos, rt)
    qi = _mm(cq, w["wiq"]).reshape(n, J, di)
    qi = jnp.concatenate([rope(qi[..., :dr], pos, rt), qi[..., dr:]],
                         axis=-1)
    wj = _mm(h, w["wiw"]) / math.sqrt(J * di)
    causal = key_pos[None, :] <= pos[:, None]
    # fault index_skips_last_block: the ``skip`` positions before a
    # query are never scored (skip 0: the sound mask)
    allowed = causal & ((key_pos[None, :] <= pos[:, None] - knobs["skip"])
                        | (key_pos[None, :] == pos[:, None]))

    def index_group(j0):
        qg = jax.lax.dynamic_slice_in_dim(qi, j0, hg, axis=1)
        wg = jax.lax.dynamic_slice_in_dim(wj, j0, hg, axis=1)
        return jnp.sum(jax.nn.relu(jnp.einsum(
            "tjd,sd->tjs", qg, ki, precision=HIGHEST)) * wg[:, :, None],
            axis=1)

    I_all = jnp.sum(jax.lax.map(index_group, jnp.arange(0, J, hg)), axis=0)
    I = jnp.where(allowed, I_all, -jnp.inf)
    if b > K:
        kth = jax.lax.top_k(I, K)[0][:, -1:]
        sel = allowed & (I >= kth)

        def swapped(sel):
            # fault index_swaps_tenth: a tenth of the kept keys (never
            # the query's own position) give way to as many of those
            # left out, whatever their scores
            noise = _noise(pos[:, None], key_pos[None, :])
            gone = sel & (noise < 0.1) & (key_pos[None, :] != pos[:, None])
            left = jnp.where(allowed & ~sel, noise, 2.0)
            n_gone = jnp.minimum(jnp.sum(gone, -1), jnp.sum(left < 2.0, -1))
            bar = jnp.take_along_axis(
                jnp.sort(left, axis=-1),
                jnp.maximum(n_gone - 1, 0)[:, None], axis=-1)
            return (sel & ~gone) | ((left <= bar) & (n_gone > 0)[:, None])

        sel = jax.lax.cond(knobs["swap"], swapped, lambda s: s, sel)
    else:
        sel = allowed
    # the audited rows: what the reference's own scores say of a given
    # cut, then the cut itself in the row's place
    want = jnp.zeros((A, b), bool).at[
        jnp.arange(A)[:, None], jnp.clip(a_sel, 0, b - 1)].max(
            (a_sel >= 0) & (a_sel < b))
    I_true = jnp.where(causal, I_all, -jnp.inf)[rows]
    cuts_i = jax.vmap(lambda s, ch: _cut_readings(s, ch, K))(I_true, want)
    sel = sel.at[put].set(want, mode="drop")
    top_v, top_i = jax.lax.top_k(
        jnp.where(sel[rows], I_all[rows], -jnp.inf), min(K, b))
    selected = jnp.where(top_v > -jnp.inf, top_i, -1)
    wkvb = _f32(w["wkv_b"]).reshape(R, H, dn + dv)

    def head_group(h0):
        wg = jax.lax.dynamic_slice_in_dim(wkvb, h0, hg, axis=1)
        kv = jnp.einsum("sr,rhd->shd", ckv, wg, precision=HIGHEST)
        qn = jax.lax.dynamic_slice_in_dim(q, h0, hg, axis=1)[..., :dn]
        qr = jax.lax.dynamic_slice_in_dim(q_rope, h0, hg, axis=1)
        s = (jnp.einsum("thd,shd->ths", qn, kv[..., :dn],
                        precision=HIGHEST)
             + jnp.einsum("thd,sd->ths", qr, kr, precision=HIGHEST)) \
            / math.sqrt(dn + dr)
        pa = jax.nn.softmax(jnp.where(sel[:, None, :], s, -jnp.inf),
                            axis=-1)
        return jnp.einsum("ths,shd->thd", pa, kv[..., dn:],
                          precision=HIGHEST)

    o = jax.lax.map(head_group, jnp.arange(0, H, hg))     # [H/hg, n, hg, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(n, H * dv)
    x1 = x + _mm(o, w["wo"])
    h2 = rms_norm(x1, w["ln2_g"], eps)
    k = c["per_token"]
    audit = {"selected": selected, "h2": h2[rows],
             "picks": jnp.zeros((A, k), jnp.int32),
             "gates": jnp.zeros((A, k), jnp.float32),
             "cuts": jnp.where((on & a_given)[:, None], jnp.concatenate(
                 [cuts_i, jnp.zeros((A, 2))], axis=-1), 0.0)}
    if dense:
        return x1 + _gated(h2, w["w_gate"], w["w_up"], w["w_down"]), \
            audit, jnp.int32(0)
    # control bf16_router: the router product's operands rounded
    rr = knobs["round_router"]
    wr = _f32(w["router"])
    sc = jax.nn.sigmoid(jnp.matmul(
        jnp.where(rr, _r(h2, jnp.bfloat16), h2),
        jnp.where(rr, _r(wr, jnp.bfloat16), wr), precision=HIGHEST))
    pick_true = sc + _f32(w["router_b"])
    # fault router_ignores_bias: the choice by the score alone
    pick = jnp.where(knobs["no_bias"], sc, pick_true)
    _, ex = jax.lax.top_k(pick, k)
    chosen = jnp.zeros(sc.shape, bool).at[
        jnp.arange(n)[:, None], ex].set(True)
    want_e = jnp.zeros((A, sc.shape[1]), bool).at[
        jnp.arange(A)[:, None], a_pick].set(True)
    cuts_r = jax.vmap(lambda s, ch: _cut_readings(s, ch, k))(
        pick_true[rows], want_e)[:, :2]
    chosen = chosen.at[put].set(want_e, mode="drop")
    gate = c["route_scale"] * sc * chosen / jnp.sum(
        sc * chosen, axis=-1, keepdims=True)
    g_top, e_top = jax.lax.top_k(jnp.where(chosen[rows], pick[rows],
                                           -jnp.inf), k)
    audit["picks"] = e_top.astype(jnp.int32)
    audit["gates"] = jnp.take_along_axis(gate[rows], e_top, axis=-1)
    audit["cuts"] = jnp.where((on & a_given)[:, None], jnp.concatenate(
        [cuts_i, cuts_r], axis=-1), 0.0)
    cap = min(cap, n)

    def expert(y, e):
        # fault dropped_expert: held expert ``drop`` adds nothing
        col = jnp.where(e == knobs["drop"], 0.0, gate[:, c["first"] + e])
        g_top, tok = jax.lax.top_k(col, cap)
        out = _gated(h2[tok], w["we_gate"][e], w["we_up"][e],
                     w["we_down"][e])
        return y.at[tok].add(g_top[:, None] * out), jnp.sum(col > 0)

    y, counts = jax.lax.scan(expert, jnp.zeros_like(h2),
                             jnp.arange(c["held"]))
    if c["n_shared"]:
        y = y + _gated(h2, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x1 + y, audit, jnp.maximum(jnp.max(counts) - cap, 0)


def cache_rows(theta: Mapping[str, Any], cfg: Mapping[str, Any], i: int,
               x, pos) -> Any:
    """Layer ``i``'s cached quantities of tokens with layer inputs
    ``x: [n, D]`` at positions ``pos: [n]``: ``[ckv | kr | ki]``,
    ``[n, kv_rank + d_rope + idx_dim]``."""
    c = dict(cfg)
    c.pop("n_items", None)
    w = {name[len(f"l{i}_"):]: v for name, v in theta.items()
         if name.startswith(f"l{i}_")}
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(_keys_block(
            w, _f32(x), jnp.asarray(pos, jnp.int32),
            cfg_key=tuple(sorted(c.items())), cache_dtype=None), axis=-1)


def forward(theta: Mapping[str, Any], ids, cfg: Mapping[str, Any], *,
            at: Optional[Sequence[int]] = None,
            given: Optional[Mapping[int, Mapping[str, Any]]] = None,
            q_block: Optional[int] = None,
            head_group: Optional[int] = None,
            key_block: Optional[int] = None, cache_dtype=None,
            router_dtype=None, fault: Optional[str] = None,
            fault_block: int = 0, fault_expert: int = 0) -> Dict[str, Any]:
    """The whole history ``ids: [L]`` through every layer. Returns, for
    the positions ``at`` (default the last), ``hidden`` (the final-normed hidden states),
    ``scores`` (those against the output table's first ``n_items``
    rows), ``layers`` (``[n_layers, len(at), D]``: the residual stream
    after every layer), ``audit[pos] = {"selected" [n_layers, K]
    positions attended (-1: none), "lat" [n_layers, kv_rank + d_rope]
    and "ik" [n_layers, idx_dim] the position's cached quantities,
    "picks" / "gates" [n_expert_layers, k], "h2" [n_expert_layers, D]
    the router's input}`` and ``cuts``.

    ``given[pos] = {"selected": [n_layers, K] positions (-1: none),
    "picks": [n_expert_layers, k] expert ids}`` (``pos`` one of ``at``)
    makes position ``pos`` attend over THAT selection and route to
    THOSE experts (the served program's cuts, so that a near-tie taken
    the other way round is not a difference), and ``cuts[pos]`` says
    how far each given cut is from the reference's own
    (``_cut_readings``, the worst over layers): ``index_low``,
    ``index_out``, ``index_regret``, ``router_low``, ``router_out``.

    ``q_block`` / ``head_group`` compute the queries a block at a time
    and the heads (the indexer's too) a group at a time against the
    whole history: the same numbers, ``[block, group, L]`` scores
    instead of ``[L, H, L]``. The history is padded to whole blocks
    (of keys too, where ``key_block`` is given; the padding comes
    after every real position, which cannot see it). A block of
    queries reads the keys up to its own end, rounded up to
    ``key_block`` (the mask hides the rest; every distinct length is
    a compile)."""
    c = dict(cfg)
    L = int(ids.shape[0])
    H = c["n_heads"]
    qb = min(int(q_block or L), L)
    hg = int(head_group or H)
    if H % hg or c["idx_heads"] % min(hg, c["idx_heads"]):
        raise ValueError("head_group divides the heads and the indexer's")
    step = int(key_block or 1)
    Lp = -(-L // max(qb, step)) * max(qb, step) if step > 1 \
        else -(-L // qb) * qb
    given = dict(given or {})
    at = [L - 1] if at is None else list(dict.fromkeys(int(a) for a in at))
    if set(given) - set(at):
        raise ValueError("a given position is one of ``at``")
    cuts = {p: dict.fromkeys(CUTS, 0.0) for p in given}
    n_items = int(c.pop("n_items", theta["out_emb"].shape[0]))
    static = dict(cfg_key=tuple(sorted(c.items())), hg=hg,
                  cap=max(1, qb // 2))
    knobs = knobs_of(fault, router_dtype, fault_block, fault_expert)
    pos = jnp.arange(Lp, dtype=jnp.int32)
    ids_p = jnp.zeros((Lp,), jnp.int32).at[:L].set(jnp.asarray(ids))
    x = _f32(jnp.take(theta["item_emb"], ids_p, axis=0))
    blocks = [(a, a + qb) for a in range(0, Lp, qb)]
    K, k = c["idx_topk"], c["per_token"]
    audit = {p: {"selected": [], "picks": [], "gates": [], "h2": []}
             for p in at}
    rows = []
    layers = []
    at_ix = jnp.asarray(at)
    with jax.default_matmul_precision("highest"):
        for i in range(c["n_layers"]):
            dense = i < c["n_dense"]
            w = {name[len(f"l{i}_"):]: v for name, v in theta.items()
                 if name.startswith(f"l{i}_")}
            kw = dict(static, dense=dense)
            keys = [_keys_block(w, x[a:b], pos[a:b],
                                cfg_key=static["cfg_key"],
                                cache_dtype=cache_dtype)
                    for a, b in blocks]
            ckv, kr, ki = (jnp.concatenate([blk[j] for blk in keys])
                           for j in range(3))
            if fault == "stale_row":
                stale = jnp.arange(7, Lp, 8)
                ckv, kr, ki = (v.at[stale].set(v[stale - 1])
                               for v in (ckv, kr, ki))
            rows.append(np.asarray(jnp.concatenate(
                [ckv[at_ix], kr[at_ix], ki[at_ix]], axis=-1)))
            out = []
            for a, b_end in blocks:
                b = min(Lp, -(-b_end // step) * step)
                mine = sorted({p for p in at if a <= p < b_end})
                # (whole multiples of AUDITED: every distinct count
                # would be a compile)
                A = -(-max(len(mine), 1) // AUDITED) * AUDITED
                a_rows = np.full((A,), -1, np.int32)
                a_given = np.zeros((A,), bool)
                a_sel = np.full((A, min(K, b)), -1, np.int32)
                a_pick = np.zeros((A, k), np.int32)
                for g, p in enumerate(mine):
                    a_rows[g] = p - a
                    if p not in given:
                        continue
                    a_given[g] = True
                    sel = np.asarray(given[p]["selected"][i])
                    sel = sel[sel >= 0][:a_sel.shape[1]]
                    a_sel[g, :len(sel)] = sel
                    if not dense:
                        a_pick[g] = np.asarray(
                            given[p]["picks"][i - c["n_dense"]])
                args = (w, x[a:b_end], pos[a:b_end], ckv[:b], kr[:b],
                        ki[:b], a_rows, a_given, a_sel, a_pick, knobs)
                xo, au, over = _query_block(*args, **kw)
                if int(over):
                    # an expert more than half of the block's tokens
                    # picked: every token through every expert
                    xo, au, _ = _query_block(*args, **dict(kw, cap=qb))
                out.append(xo)
                if mine:
                    au = jax.device_get(au)
                for g, p in enumerate(mine):
                    audit[p]["selected"].append(au["selected"][g])
                    if not dense:
                        for name in ("picks", "gates", "h2"):
                            audit[p][name].append(au[name][g])
                    if p in given:
                        for j, name in enumerate(CUTS):
                            cuts[p][name] = max(cuts[p][name],
                                                float(au["cuts"][g, j]))
            x = jnp.concatenate(out)
            layers.append(x[at_ix])
        hidden = rms_norm(x[at_ix], theta["ln_f_g"], c["norm_eps"])
        scores = _mm(hidden, _f32(theta["out_emb"][:n_items]).T)

    def stack(rows, width):
        rows = [np.pad(r, (0, width - len(r)), constant_values=-1)
                if r.ndim == 1 and len(r) < width else r for r in rows]
        return np.stack(rows) if rows else np.zeros((0, width), np.int32)

    rows = np.stack(rows)
    return {"hidden": hidden, "scores": scores, "layers": jnp.stack(layers),
            "audit": {p: {"lat": rows[:, j, :-c["idx_dim"]],
                          "ik": rows[:, j, -c["idx_dim"]:],
                          "selected": stack(v["selected"], min(K, Lp)),
                          "picks": stack(v["picks"], k),
                          "gates": stack(v["gates"], k),
                          "h2": stack(v["h2"], int(c["width"]))}
                      for j, (p, v) in enumerate(audit.items())},
            "cuts": cuts}
