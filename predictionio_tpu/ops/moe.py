"""Sparse-expert feed-forward layer: router, dropless token dispatch,
grouped matmul, combine — forward and backward.

The layer OLMoE-1B-7B (and the expert models after it in ROADMAP
"Reach") is built from. Per token: router logits ``h @ W_r`` in
float32, softmax over the experts, the top ``k`` of them, their
probabilities used as they are (NOT renormalised: OLMoE's
``norm_topk_prob: false``); each chosen expert computes
``W_down(silu(W_gate x) * W_up x)``; the output is the weighted sum.

Dropless: every (token, expert) pair is computed. The ``T * k`` pairs
are sorted by expert, so that each expert owns one contiguous run of
rows whatever its load, and the three expert matmuls run as GROUPED
matmuls over those runs (``group_sizes`` says where each run ends; one
expert may own every row, another none). No capacity factor, no
dropped token; the counters below let a caller assert it.

Two grouped-matmul backends, chosen from the platform:
``megablox`` (``jax.experimental.pallas.ops.tpu.megablox``: the Pallas
TPU kernels ``gmm`` / ``tgmm``, under a custom VJP of this module so
that forward and the two backward products are named apart in a
trace) and ``ragged`` (``jax.lax.ragged_dot``: XLA, any backend, the
CPU tests' path and the kernels' oracle).

Dispatch and combine are permutations, so both directions are written
as GATHERS (a custom VJP whose backward gathers with the inverse
permutation) instead of leaving XLA a scatter-add with repeated
indices for the transpose.
"""

from __future__ import annotations

import functools
import importlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# the package re-exports its ``gmm`` FUNCTION under the module's name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

# m, k, n tile of the megablox kernels at the OLMoE widths on a v5e
# (the (128, 128, 128) default re-reads each operand 8-16 times; of
# five tiles tried on the chip, PR 25, these were the fastest: 133 and
# 96 TFLOP/s at 65,536 rows)
GMM_TILING = (256, 2048, 1024)
# the weight-gradient kernel holds a float32 [k tile, n tile] block and
# its accumulator in VMEM: (512, 1024, 1024) does not fit its 16 MiB
TGMM_TILING = (256, 1024, 1024)


def resolve_gmm_impl(impl: str = "auto") -> str:
    if impl != "auto":
        return impl
    return "megablox" if jax.default_backend() == "tpu" else "ragged"


def _fit_tiling(m: int, k: int, n: int,
                tiling: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Clip a tile to the problem (the kernels want ``m`` divisible by
    the m tile; k and n tiles may be ragged but not larger)."""
    tm, tk, tn = tiling
    tm = min(tm, m)
    while m % tm:
        tm //= 2
    return max(tm, 8), min(tk, k), min(tn, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _megablox_gmm(lhs, rhs, rhs_low, group_sizes, tiling, interpret):
    """``rhs`` is the (float32 master) weight the gradient is for;
    ``rhs_low`` its copy in ``lhs``'s dtype, which is what the kernels
    multiply. The caller may make that copy once for many calls (a
    step's microbatches, an encode's calls); None makes it here."""
    if rhs_low is None:
        rhs_low = rhs.astype(lhs.dtype)
    m, k = lhs.shape
    return _megablox.gmm(lhs, rhs_low, group_sizes, lhs.dtype,
                         _fit_tiling(m, k, rhs.shape[2], tiling),
                         interpret=interpret)


def _megablox_gmm_fwd(lhs, rhs, rhs_low, group_sizes, tiling, interpret):
    if rhs_low is None:
        rhs_low = rhs.astype(lhs.dtype)
    out = _megablox_gmm(lhs, rhs, rhs_low, group_sizes, tiling, interpret)
    # the master weight itself is not kept: its dtype is all the
    # backward pass needs of it
    return out, (lhs, rhs_low, group_sizes, jnp.zeros((0,), rhs.dtype))


def _megablox_gmm_bwd(tiling, interpret, res, grad):
    lhs, rhs_low, group_sizes, like = res
    m, k = lhs.shape
    n = rhs_low.shape[2]
    with jax.named_scope("gmm_dlhs"):
        d_lhs = _megablox.gmm(grad, rhs_low, group_sizes, lhs.dtype,
                              _fit_tiling(m, n, k, tiling),
                              transpose_rhs=True, interpret=interpret)
    with jax.named_scope("gmm_drhs"):
        # the weight gradient leaves the kernel in the master weight's
        # dtype (float32): it is summed over microbatches as it is
        d_rhs = _megablox.tgmm(lhs.swapaxes(0, 1), grad, group_sizes,
                               like.dtype,
                               _fit_tiling(m, k, n, TGMM_TILING),
                               num_actual_groups=rhs_low.shape[0],
                               interpret=interpret)
    return d_lhs, d_rhs, None, None


_megablox_gmm.defvjp(_megablox_gmm_fwd, _megablox_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, rhs_low=None,
                   impl: str = "auto",
                   tiling: Tuple[int, int, int] = GMM_TILING,
                   interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[g(r)]`` where rows are grouped in runs:
    the first ``group_sizes[0]`` rows use ``rhs[0]``, the next
    ``group_sizes[1]`` use ``rhs[1]``, ... ``lhs: [m, k]``, ``rhs: [G,
    k, n]``, ``group_sizes: [G]`` int32 summing to ``m``. The operands
    are multiplied in ``lhs``'s dtype (``rhs_low``: ``rhs`` already in
    it, else cast here), products accumulate in float32, the result has
    ``lhs``'s dtype; the gradient for ``rhs`` comes in ``rhs``'s."""
    impl = resolve_gmm_impl(impl)
    if impl == "megablox":
        return _megablox_gmm(lhs, rhs, rhs_low, group_sizes, tuple(tiling),
                             bool(interpret))
    if impl != "ragged":
        raise ValueError(f"unknown grouped-matmul backend {impl!r}")
    precision = jax.lax.Precision.HIGHEST \
        if lhs.dtype == jnp.float32 else None
    return jax.lax.ragged_dot(
        lhs, use_low(rhs, rhs_low, lhs.dtype),
        group_sizes.astype(jnp.int32), precision=precision,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


@jax.custom_vjp
def _low_of(w, w_low):
    return w_low


def _low_of_fwd(w, w_low):
    return w_low, jnp.zeros((0,), w.dtype)


def _low_of_bwd(like, g):
    return g.astype(like.dtype), None


_low_of.defvjp(_low_of_fwd, _low_of_bwd)


def use_low(w, w_low, dtype):
    """``w`` in ``dtype`` for a matmul: the ready-made copy ``w_low``
    when there is one (the gradient still goes to ``w``), else a cast."""
    if w_low is not None:
        return _low_of(w, w_low)
    return w if w.dtype == dtype else w.astype(dtype)


# -- router ------------------------------------------------------------------

def route(h, w_router, k: int, renorm: bool = False):
    """``h: [T, D]`` -> router logits ``[T, E]`` (float32, a true
    float32 product on every backend), the top-``k`` experts ``[T, k]``
    and their softmax probabilities ``[T, k]``: as they are (OLMoE's
    ``norm_topk_prob: false``) or, with ``renorm``, divided by their
    sum (the Qwen3 / SDAR ``norm_topk_prob: true``)."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return logits, probs, experts.astype(jnp.int32), weights


def aux_losses(logits, probs, experts, valid) -> Tuple[Any, Any]:
    """(load-balancing loss, router z-loss) over the tokens ``valid``
    marks (``[T]`` 0/1). Load balancing as in the Switch / OLMoE
    recipe: ``E * sum_e f_e * P_e`` with ``f_e`` the share of (token,
    choice) pairs sent to expert ``e`` per token and ``P_e`` the mean
    router probability of ``e``; z-loss the mean squared
    log-partition of the router logits."""
    E = logits.shape[-1]
    n = jnp.maximum(jnp.sum(valid), 1.0)
    chosen = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32), axis=1)
    f = jnp.sum(chosen * valid[:, None], axis=0) / n
    p = jnp.sum(probs * valid[:, None], axis=0) / n
    lb = E * jnp.sum(f * p)
    z = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)) * valid) / n
    return lb, z


# -- dispatch / combine: permutations, gathers both ways ---------------------
#
# What these cost a v5e, measured at the OLMoE widths ([T k, 2048]
# bf16 rows, k 8; my chip runs, PR 37: each form alone, and the cell's
# trace, op by op). T 8,192 is a microbatch, 16,384 an encode call:
#
# - XLA:TPU compiles every row gather as an op of its own (a DMA
#   gather; never fused into what reads it), and its time is set by
#   WHERE ITS SOURCE LIES, not by the bytes: from a source the compiler
#   put in VMEM (h, g: [T, 2048], 33-67 MB) 65,536 / 131,072 rows take
#   0.42 / 0.83 ms (6.3 ns a row, the speed of the write); from a
#   source in HBM (ys, d_xs: [T k, 2048], 268-537 MB) 2.24 / 4.47 ms
#   (34 ns a row), bf16 or float32 alike. The un-permutations out of
#   expert order (``_combine``, ``_dispatch_bwd``) are of the second
#   kind and are over half of what dispatch and combine still cost.
#   Nothing XLA can be told makes them faster: indices that follow
#   each other, or ascend, change nothing (2.16 / 4.31); a scatter by
#   ``order`` takes 3.45 / 6.91; column slices that fit VMEM cost more
#   in slicing than they save (3.9); rows laid out as one 4 KB tile
#   each ([T k, 16, 128]) gather in 1.19 but the two layout copies
#   cost 0.82 each; two hops through token chunks that fit VMEM 5.2.
# - The reduction over a token's k gathered rows is NOT the slow part:
#   0.48 / 0.96 ms, the speed of its bytes, whether the block is
#   [T, k, D] summed over the middle or [k, T, D] added slab by slab,
#   behind an ``optimization_barrier`` or written ``tk,tkd->td`` (the
#   compiler's own estimate for it, 2.0M / 4.0M cycles, is four times
#   too high). Re-spelling it buys nothing; do not try again.
# - What indexes SCALARS one by one is slow out of all proportion: a
#   scatter-add of 65,536 / 131,072 ones into 64 bins (``bincount``)
#   0.57 / 1.14 ms, a scatter of as many int32 0.30 / 0.61, a gather
#   of as many float32 0.47; a sort of as many keys with a payload
#   0.045 / 0.10. So the plan and the backward pass move scalars by
#   SORTING (``_place``) and count by compare-and-sum.

def _place(values, to):
    """``out[to[i]] = values[i]`` for a permutation ``to`` of scalars,
    as a SORT by ``to`` that carries ``values`` (a tenth of XLA's
    scatter or gather of as many scalars: the section comment). With
    ``to`` a permutation's inverse it is ``values[perm]``."""
    return jax.lax.sort((to, values), num_keys=1)[1]


def dispatch_plan(experts, n_experts: int) -> Dict[str, Any]:
    """From ``experts: [T, k]`` the plan of a dropless dispatch:
    ``order`` (pair indices sorted by expert, stable, so a token's
    pairs keep their order inside an expert), its inverse ``inv``, the
    token of each sorted row and ``group_sizes`` ``[E]`` (their sum is
    ``T * k``: nothing is dropped). Two sorts and a compare-and-sum,
    no scatter and no ``bincount`` (the section comment)."""
    T, k = experts.shape
    flat = experts.reshape(-1).astype(jnp.int32)
    pair = jnp.arange(T * k, dtype=jnp.int32)
    _, order = jax.lax.sort((flat, pair), num_keys=1, is_stable=True)
    inv = _place(pair, order)
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    return {"order": order, "inv": inv, "token": order // k,
            "group_sizes": group_sizes}


def _rows(x, idx):
    """``x[idx]`` for indices known to be in range (permutations and
    their quotients). ``jnp.take``'s default fills out-of-range rows
    with NaN, and that select costs the TPU 2.8 times the gather (2.55
    against 0.91 ms for 131,072 rows of 2,048 bf16 out of 16,384: my
    chip run, PR 25). That 0.91 ms is a gather whose SOURCE fits VMEM;
    the same 131,072 rows out of a 537 MB source in HBM take 4.47 ms
    (the section comment; my chip runs, PR 37)."""
    return jnp.take(x, idx, axis=0, mode="clip")


@jax.custom_vjp
def _dispatch(x, token, inv):
    return _rows(x, token)


def _dispatch_fwd(x, token, inv):
    return _dispatch(x, token, inv), (inv, x.shape[0])


def _dispatch_bwd(res, g):
    inv, T = res
    k = inv.shape[0] // T
    back = _rows(g, inv).reshape(T, k, g.shape[-1])
    return (jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weights, order, inv):
    T, k = weights.shape
    back = _rows(ys, inv).reshape(T, k, ys.shape[-1])
    return jnp.sum(back.astype(jnp.float32)
                   * weights[..., None].astype(jnp.float32), axis=1)


def _combine_fwd(ys, weights, order, inv):
    return _combine(ys, weights, order, inv), (ys, weights, order, inv)


def _combine_bwd(res, g):
    ys, weights, order, inv = res
    T, k = weights.shape
    # ``g``'s rows in expert order, gathered once for both gradients
    gs = _rows(g.astype(jnp.float32), order // k)
    w_sorted = _place(weights.reshape(-1), inv)
    d_ys = (gs * w_sorted[:, None].astype(jnp.float32)).astype(ys.dtype)
    # the weights' gradient where the rows lie, <ys[r], g[token[r]]>,
    # in the pass that writes ``d_ys``; then T k scalars to token order
    dw_sorted = jnp.sum(ys.astype(jnp.float32) * gs, axis=-1)
    d_w = _place(dw_sorted, order).reshape(T, k)
    return d_ys, d_w.astype(weights.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# -- the layer ---------------------------------------------------------------

def moe_ffn(h, w_router, w_gate, w_up, w_down, *, k: int,
            compute_dtype=None, valid: Optional[Any] = None,
            low: Optional[Tuple[Any, Any, Any]] = None):
    """The expert layer on ``h: [T, D]`` (already normalised).

    ``w_router: [D, E]``, ``w_gate`` / ``w_up``: ``[E, D, F]``,
    ``w_down: [E, F, D]`` (``low``: the three already in the compute
    dtype). Returns ``(y [T, D] float32, stats)`` where
    ``stats`` holds ``logits``, the two auxiliary losses (over
    ``valid`` tokens; all of them when None), ``group_sizes`` ``[E]``
    and ``dropped`` (pairs not computed: ``T * k`` less the rows the
    grouped matmuls covered; 0 by construction, reported so that a
    caller can assert it)."""
    T, _ = h.shape
    E = w_router.shape[1]
    cd = compute_dtype or h.dtype
    with jax.named_scope("moe/router"):
        logits, probs, experts, weights = route(h, w_router, k)
        ones = jnp.ones((T,), jnp.float32) if valid is None \
            else valid.astype(jnp.float32)
        lb, z = aux_losses(logits, probs, experts, ones)
    with jax.named_scope("moe/dispatch"):
        plan = dispatch_plan(experts, E)
        xs = _dispatch(h.astype(cd), plan["token"], plan["inv"])
    gs = plan["group_sizes"]
    gmm = functools.partial(grouped_matmul, group_sizes=gs)
    low = low or (None, None, None)
    with jax.named_scope("moe/gmm_gate_up"):
        gate = gmm(xs, w_gate, rhs_low=low[0])
        up = gmm(xs, w_up, rhs_low=low[1])
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(cd)
    with jax.named_scope("moe/gmm_down"):
        ys = gmm(act, w_down, rhs_low=low[2])
    with jax.named_scope("moe/combine"):
        y = _combine(ys, weights, plan["order"], plan["inv"])
    stats = {"logits": logits, "experts": experts, "lb_loss": lb,
             "z_loss": z, "group_sizes": gs,
             "dropped": jnp.int32(T * k) - jnp.sum(gs)}
    return y, stats


# the experts' gate activation, by name (data of the call): SiLU (OLMoE,
# GLM-5, SDAR) or ReLU (SmallThinker's ReGLU)
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_ffn_dense(h, w_router, w_gate, w_up, w_down, *, k: int,
                  renorm: bool = False, activation: str = "silu",
                  router_input=None):
    """The same layer the plain way: every expert on every token, the
    result masked to the top ``k``. ``O(E / k)`` times the work; the
    dispatch path's oracle in the tests. ``router_input``: what the
    router reads where that is not ``h`` (a router placed before
    attention)."""
    hp = jax.lax.Precision.HIGHEST
    _, probs, experts, weights = route(
        h if router_input is None else router_input, w_router, k,
        renorm=renorm)
    E = w_router.shape[1]
    gate = jnp.einsum("td,edf->tef", h, w_gate, precision=hp)
    up = jnp.einsum("td,edf->tef", h, w_up, precision=hp)
    out = jnp.einsum("tef,efd->ted", ACTIVATIONS[activation](gate) * up,
                     w_down, precision=hp)
    w_full = jnp.sum(jax.nn.one_hot(experts, E, dtype=h.dtype)
                     * weights[..., None], axis=1)
    return jnp.sum(out * w_full[..., None], axis=1)


# -- sigmoid router, a held share of the experts (GLM-5 family) ---------------

def route_sigmoid(h, w_router, bias, k: int, scale: float):
    """GLM-5 / DeepSeek-V3 ``noaux_tc`` routing of ``h: [T, D]`` over
    ALL the published experts (``w_router: [D, E]``): scores
    ``sigmoid(h @ W_r)`` in float32; the ``k`` experts with the
    largest ``score + bias`` (``bias: [E]``, the correction that moves
    the choice and never the weight); weights ``scale * s_i /
    sum_chosen s`` (``norm_topk_prob`` with ``routed_scaling_factor``).
    Returns (scores ``[T, E]``, experts ``[T, k]`` int32, weights
    ``[T, k]`` float32)."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return scores, experts.astype(jnp.int32), weights


def moe_ffn_share(h, experts, weights, w_gate, w_up, w_down, *,
                  first: int, compute_dtype=None, activation: str = "silu"):
    """The routed part of an expert layer that HOLDS experts ``first
    .. first + E_held`` of those the router chose among (``w_gate`` /
    ``w_up``: ``[E_held, D, F]``, ``w_down: [E_held, F, D]``, already
    in the compute dtype): the sum over a token's picks that live here
    of ``weight * expert(h)``; a pick of an expert held elsewhere adds
    nothing (its chip adds it in the deployment; summed over all
    shares the parts are the uncut layer). The dispatch plan, grouped
    matmuls and combine are :func:`moe_ffn`'s: absent picks sort into
    a last, weightless run that no group covers and whose rows are
    zeroed. ``activation`` names the gate's (:data:`ACTIVATIONS`).
    Returns ``(y [T, D] float32, local [T, k] bool, group_sizes
    [E_held])``."""
    T, k = experts.shape
    held = w_gate.shape[0]
    cd = compute_dtype or h.dtype
    local = (experts >= first) & (experts < first + held)
    with jax.named_scope("moe/dispatch"):
        plan = dispatch_plan(jnp.where(local, experts - first, held),
                             held + 1)
        xs = _dispatch(h.astype(cd), plan["token"], plan["inv"])
    gs = plan["group_sizes"][:held]
    covered = (jnp.arange(T * k) < jnp.sum(gs))[:, None]
    gmm = functools.partial(grouped_matmul, group_sizes=gs)
    with jax.named_scope("moe/gmm_gate_up"):
        gate = jnp.where(covered, gmm(xs, w_gate, rhs_low=w_gate), 0)
        up = jnp.where(covered, gmm(xs, w_up, rhs_low=w_up), 0)
        act = (ACTIVATIONS[activation](gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(cd)
    with jax.named_scope("moe/gmm_down"):
        ys = jnp.where(covered, gmm(act, w_down, rhs_low=w_down), 0)
    with jax.named_scope("moe/combine"):
        y = _combine(ys, jnp.where(local, weights, 0.0), plan["order"],
                     plan["inv"])
    return y, local, gs


__all__ = [
    "ACTIVATIONS",
    "GMM_TILING",
    "aux_losses",
    "dispatch_plan",
    "grouped_matmul",
    "moe_ffn",
    "moe_ffn_dense",
    "moe_ffn_share",
    "resolve_gmm_impl",
    "route",
    "route_sigmoid",
    "use_low",
]
