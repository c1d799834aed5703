"""Attention ops: reference MHA + ring attention for sequence parallelism.

The reference framework has no sequence models at all (SURVEY §2.6: SP/CP
row = "No"), so this module is net-new capability the TPU build is required
to carry: long-context attention that scales past one device's HBM by
sharding the SEQUENCE dimension over the mesh and rotating K/V blocks
around the ring with ``jax.lax.ppermute`` (Liu et al., "Ring Attention
with Blockwise Transformers"; see PAPERS.md).

Design notes (TPU-first):
- The per-step block computation is two einsums + online-softmax updates —
  all MXU/VPU work with static shapes; the ring rotation is a ``ppermute``
  that XLA overlaps with compute over ICI.
- Online softmax keeps running (max, denominator, numerator) so no
  [L, L_global] score matrix ever materializes: memory is O(L_local²
  per-step block), which is what makes million-token contexts feasible.
- Causal masking uses global positions derived from the device's ring
  index, so the sharded result is bit-for-bit the same computation as the
  dense reference (up to float reduction order).

Layout convention: ``[batch, heads, seq, head_dim]``; the sequence axis is
the sharded one in the ring variant.
"""

from __future__ import annotations

import functools
from typing import Optional


def mha_reference(q, k, v, causal: bool = False, scale: Optional[float] = None,
                  key_padding_mask=None):
    """Dense multi-head attention oracle: softmax(QKᵀ·scale [+mask]) V.

    ``q/k/v: [B, H, L, D]``. Used as the numerical reference for the ring
    variant and fine on its own for short sequences.

    ``key_padding_mask``: optional ``[B, L_k]`` (1/True = real key,
    0/False = padding). Masked keys score ``-inf`` before the softmax,
    composed with the causal mask — ragged sequences batched into one
    padded table must not attend their pad rows. A query row whose
    visible keys are ALL masked outputs exact zeros (safe softmax)
    instead of NaN; without a mask the historical code path is
    untouched.
    """
    import jax
    import jax.numpy as jnp

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(lq)[:, None]
        kpos = jnp.arange(lk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    if key_padding_mask is None:
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                          precision=jax.lax.Precision.HIGHEST)
    kp = jnp.asarray(key_padding_mask)
    s = jnp.where(kp[:, None, None, :].astype(bool), s, -jnp.inf)
    # safe softmax: a fully-masked query row (all -inf) outputs 0, the
    # same convention as the ring variant's zero-denominator rows
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(s - m))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def _ring_attention_local(q, k, v, kv_mask=None, *, axis_name: str,
                          axis_size: int, causal: bool, scale: float):
    """Per-device ring attention body (runs under shard_map).

    ``q/k/v: [B, H, L_local, D]`` — this device's sequence shard. Each of
    the ``axis_size`` steps attends Q against the currently-held K/V block,
    folds the result into online-softmax accumulators, then rotates K/V to
    the next device on the ring. ``kv_mask`` (``[B, L_local]``, optional)
    is this device's slice of the key-padding mask; it rotates around the
    ring WITH its K/V block so each fold masks the block it actually
    holds.
    """
    import jax
    import jax.numpy as jnp

    B, H, L, D = q.shape
    my_idx = jax.lax.axis_index(axis_name)
    hi = jax.lax.Precision.HIGHEST

    # accumulators: numerator [B,H,L,D], denominator + running max [B,H,L].
    # Mark the (device-constant) initializers as varying over the ring
    # axis so the fori_loop carry type matches its per-device outputs.
    _vary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    o0 = _vary(jnp.zeros((B, H, L, D), dtype=jnp.float32))
    l0 = _vary(jnp.zeros((B, H, L), dtype=jnp.float32))
    m0 = _vary(jnp.full((B, H, L), -jnp.inf, dtype=jnp.float32))

    qpos = my_idx * L + jnp.arange(L)  # global query positions

    def fold(i, o, l, m, k_blk, v_blk, mask_blk):
        """Fold the currently-held K/V block into the accumulators.
        The block held at step i originated on device (my_idx - i) % n."""
        src = (my_idx - i) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k_blk.astype(jnp.float32), precision=hi) * scale
        if causal:
            kpos = src * L + jnp.arange(L)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        if mask_blk is not None:
            s = jnp.where(mask_blk[:, None, None, :].astype(bool),
                          s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(m - m_new))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isneginf(m_new[..., None]), 0.0, p)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32), precision=hi)
        return o_new, l_new, m_new

    # fori_loop: one compiled step regardless of ring size. Runs n-1
    # fold+rotate steps; the LAST fold is peeled outside the loop so no
    # dead final rotation ships K/V over ICI just to be discarded.
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    if kv_mask is None:
        def body(i, carry):
            o, l, m, k_blk, v_blk = carry
            o, l, m = fold(i, o, l, m, k_blk, v_blk, None)
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            return o, l, m, k_blk, v_blk

        o, l, m, k_last, v_last = jax.lax.fori_loop(
            0, axis_size - 1, body, (o0, l0, m0, k, v))
        o, l, m = fold(axis_size - 1, o, l, m, k_last, v_last, None)
    else:
        def body(i, carry):
            o, l, m, k_blk, v_blk, mask_blk = carry
            o, l, m = fold(i, o, l, m, k_blk, v_blk, mask_blk)
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
            return o, l, m, k_blk, v_blk, mask_blk

        o, l, m, k_last, v_last, mask_last = jax.lax.fori_loop(
            0, axis_size - 1, body,
            (o0, l0, m0, k, v, kv_mask.astype(jnp.float32)))
        o, l, m = fold(axis_size - 1, o, l, m, k_last, v_last, mask_last)
    # rows with no visible keys (every key padding-masked; can't happen
    # causally WITHOUT a mask: the self-block is always visible) keep
    # denominator 0 -> output 0, matching mha_reference's safe softmax
    denom = jnp.where(l == 0.0, 1.0, l)
    return (o / denom[..., None]).astype(q.dtype)


def _sp_program(local_body, mesh, axis_name: str, with_mask: bool = False):
    """shard_map + jit a per-device attention body with q/k/v/out all
    sequence-sharded over ``axis_name`` — the shared scaffolding of both
    SP schemes. ``with_mask`` adds a fourth ``[B, L]`` input sharded
    over the same sequence axis (the key-padding mask)."""
    import jax
    from jax.sharding import PartitionSpec as P

    in_specs = (P(None, None, axis_name, None),) * 3
    if with_mask:
        in_specs = in_specs + (P(None, axis_name),)
    fn = jax.shard_map(
        local_body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(None, None, axis_name, None),
    )
    return jax.jit(fn)


def _sp_call(program, q, k, v, mesh, axis_name: str, kv_mask=None):
    """Stage the global arrays sequence-sharded and run the program."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by mesh axis "
            f"{axis_name} of size {n}")
    spec = NamedSharding(mesh, P(None, None, axis_name, None))
    q, k, v = (jax.device_put(x, spec) for x in (q, k, v))
    if kv_mask is None:
        return program(q, k, v)
    import jax.numpy as jnp

    mask_spec = NamedSharding(mesh, P(None, axis_name))
    kv_mask = jax.device_put(jnp.asarray(kv_mask, dtype=jnp.float32),
                             mask_spec)
    return program(q, k, v, kv_mask)


@functools.lru_cache(maxsize=64)
def _ring_fn(mesh, axis_name: str, causal: bool, scale: float,
             masked: bool = False):
    """Cached jitted shard_map program per (mesh, axis, causal, scale,
    masked) — repeated calls (e.g. one per layer per step) hit the jit
    cache instead of retracing (same pattern as
    parallel/als_sharding.py)."""
    body = functools.partial(_ring_attention_local, axis_name=axis_name,
                             axis_size=mesh.shape[axis_name],
                             causal=causal, scale=scale)
    if not masked:
        # the UNMASKED program keeps the historical three-operand
        # signature (cached executables, HLO-inspection tests)
        return _sp_program(body, mesh, axis_name)
    return _sp_program(body, mesh, axis_name, with_mask=True)


def ring_attention(q, k, v, mesh, axis_name: str = "data",
                   causal: bool = False, scale: Optional[float] = None,
                   key_padding_mask=None):
    """Sequence-parallel attention over ``mesh[axis_name]``.

    ``q/k/v: [B, H, L, D]`` global arrays whose ``L`` must divide evenly
    by the mesh axis size; each device computes its sequence shard while
    K/V blocks rotate around the ring (ICI ppermute). Returns the global
    ``[B, H, L, D]`` result matching :func:`mha_reference`.

    ``key_padding_mask``: optional ``[B, L]`` (1 = real, 0 = padding),
    sequence-sharded like K/V; the mask block rotates around the ring
    with its K/V block, so padded keys score ``-inf`` in every fold —
    identical semantics to the dense oracle's mask.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _sp_call(
        _ring_fn(mesh, axis_name, causal, float(scale),
                 key_padding_mask is not None),
        q, k, v, mesh, axis_name, kv_mask=key_padding_mask)


# ---------------------------------------------------------------------------
# Ulysses-style all-to-all sequence parallelism
# ---------------------------------------------------------------------------

def _ulysses_local(q, k, v, kv_mask=None, *, axis_name: str, causal: bool,
                   scale: float):
    """Per-device body: all_to_all swaps the sequence shard for a HEAD
    shard, so each device runs DENSE attention for its head group over
    the FULL sequence (causal masking is then trivially exact), and a
    second all_to_all restores sequence sharding.

    Versus the ring: two all_to_all collectives total instead of P-1
    ppermute steps, and the math between them is plain unsharded
    attention — the better fit when heads divide the mesh axis and the
    full [L, L] per-head-group score block fits HBM; the ring wins on
    memory for extreme L (its online softmax never materializes
    [L, L]). The key-padding mask (``[B, L/P]`` per device) has no head
    axis to trade, so it all_gathers to the full ``[B, L]`` — tiny next
    to K/V — and feeds the dense oracle's mask path directly."""
    import jax

    def swap(x, fwd: bool):
        # [B, H, L/P, D] -> [B, H/P, L, D] (fwd) and back (not fwd)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1 if fwd else 2,
            concat_axis=2 if fwd else 1, tiled=True)

    qh, kh, vh = swap(q, True), swap(k, True), swap(v, True)
    full_mask = None
    if kv_mask is not None:
        full_mask = jax.lax.all_gather(kv_mask, axis_name, axis=1,
                                       tiled=True)
    out = mha_reference(qh, kh, vh, causal=causal, scale=scale,
                        key_padding_mask=full_mask)
    return swap(out, False)


@functools.lru_cache(maxsize=64)
def _ulysses_fn(mesh, axis_name: str, causal: bool, scale: float,
                masked: bool = False):
    body = functools.partial(_ulysses_local, axis_name=axis_name,
                             causal=causal, scale=scale)
    if not masked:
        return _sp_program(body, mesh, axis_name)
    return _sp_program(body, mesh, axis_name, with_mask=True)


def ulysses_attention(q, k, v, mesh, axis_name: str = "data",
                      causal: bool = False,
                      scale: Optional[float] = None,
                      key_padding_mask=None):
    """All-to-all sequence-parallel attention over ``mesh[axis_name]``
    (DeepSpeed-Ulysses layout; see PAPERS.md): inputs/outputs are
    sequence-sharded ``[B, H, L, D]`` exactly like
    :func:`ring_attention`, but internally each device attends its
    H/P-head group over the full sequence between two all_to_all
    collectives. Requires both ``L`` and ``H`` divisible by the axis
    size. Numerics match :func:`mha_reference`, including the optional
    ``[B, L]`` ``key_padding_mask`` (1 = real, 0 = padding)."""
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            f"head count {q.shape[1]} not divisible by mesh axis "
            f"{axis_name} of size {n} — use ring_attention for "
            "head counts below the mesh size")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _sp_call(
        _ulysses_fn(mesh, axis_name, causal, float(scale),
                    key_padding_mask is not None),
        q, k, v, mesh, axis_name, kv_mask=key_padding_mask)


# ---------------------------------------------------------------------------
# Packed rows: causal attention inside segments
# ---------------------------------------------------------------------------

def segment_attention_dense(q, k, v, segment_ids, scale: Optional[float] = None):
    """Causal attention over PACKED rows, the plain way: position ``t``
    attends ``s <= t`` of its own segment. ``q/k/v: [B, H, L, D]``;
    ``segment_ids: [B, L]`` with 0 for padding (a pad position attends
    nothing and outputs exact zeros, the safe-softmax convention of
    :func:`mha_reference`). With one segment per row (ids 1 on real
    positions) this is ``mha_reference(causal=True,
    key_padding_mask=...)`` to the last bit on real positions.
    Materialises ``[B, H, L, L]`` scores: for short rows and as the
    blocked kernel's oracle."""
    import jax
    import jax.numpy as jnp

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    L = s.shape[-1]
    seg = jnp.asarray(segment_ids)
    ok = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None] \
        & (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] != 0)
    s = jnp.where(ok[:, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(s - m))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


# q / kv block of the blocked kernel at 4k rows and 128-wide heads on a
# v5e (forward, and the fused backward)
FLASH_BLOCK = 512


@functools.lru_cache(maxsize=16)
def _splash_kernel(n_heads: int, seq_len: int, block: int, interpret: bool):
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    b = min(int(block), seq_len)
    sizes = sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, use_fused_bwd_kernel=True)
    mask = sm.MultiHeadMask(
        [sm.CausalMask((seq_len, seq_len))] * n_heads)
    # the kernel keeps its block-mask tables as arrays: built here as
    # constants even when the first caller is being traced (this cache
    # would otherwise keep that trace's tracers)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def segment_attention_flash(q, k, v, segment_ids,
                            scale: Optional[float] = None,
                            block: int = FLASH_BLOCK,
                            interpret: bool = False):
    """The same attention BLOCKED: the Pallas TPU splash-attention
    kernel (online softmax over ``block`` x ``block`` tiles, blocks
    above the diagonal skipped, segment ids compared inside the tile),
    forward and a fused backward; no ``[L, L]`` array exists. Inputs
    in the compute dtype (bf16 on the chip), products accumulated in
    float32. ``L`` a multiple of the block (or shorter than one), the
    head width a multiple of 128 lanes."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    _, H, L, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    kernel = _splash_kernel(int(H), int(L), int(block), bool(interpret))
    seg = segment_ids.astype("int32")

    def one(qi, ki, vi, si):
        return kernel(qi, ki, vi, segment_ids=sk.SegmentIds(q=si, kv=si))

    out = jax.vmap(one)((q * scale).astype(q.dtype), k, v, seg)
    # a pad position sees only pads of "segment 0": zero it, as the
    # dense form does
    return out * (seg != 0)[:, None, :, None].astype(out.dtype)


# ---------------------------------------------------------------------------
# Grouped-query attention of a few query rows over a PAGED cache
# ---------------------------------------------------------------------------

PAGED_NEG = -1e30   # "no key yet": finite, so that an empty cache is no NaN


def paged_gqa_attention_xla(q, pool_k, pool_v, table, length, *,
                            scale: float, compute_dtype=None, base=None,
                            first=None):
    """The paged attention below, the plain way (any backend; the
    kernel's oracle): the session's blocks are GATHERED out of the
    pool (``[B, S, KV, d]`` copies) and scored under a length mask
    and, with ``first``, each query row's own lower bound."""
    import jax.numpy as jnp

    B, KV, RG, d = q.shape
    bs = pool_k.shape[1]
    S = table.shape[1] * bs
    cd = jnp.dtype(compute_dtype or q.dtype)
    ks = jnp.take(pool_k, table, axis=0, mode="clip").reshape(B, S, KV, d)
    vs = jnp.take(pool_v, table, axis=0, mode="clip").reshape(B, S, KV, d)
    s = jnp.einsum("bkrd,bskd->bkrs", q.astype(cd), ks.astype(cd),
                   preferred_element_type=jnp.float32) * scale
    at = jnp.arange(S)[None, :]
    if first is None:
        ok = (at < length[:, None])[:, None, None, :]
    else:
        at = at + (0 if base is None else base[:, None])
        ok = ((at < length[:, None])[:, None, :]
              & (at[:, None, :] >= first[:, :, None]))[:, None]
    s = jnp.where(ok, s, PAGED_NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.where(ok, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bkrs,bskd->bkrd", p.astype(cd), vs.astype(cd),
                     preferred_element_type=jnp.float32)
    return acc, m, jnp.sum(p, axis=-1)


def _paged_gqa_kernel(*refs, bs: int, n_kv: int, d: int, scale: float,
                      bounded: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if bounded:
        (table_ref, nblk_ref, len_ref, start_ref, q_ref, lo_ref, k_ref,
         v_ref, acc_ref, m_ref, l_ref, acc_sc, m_sc, l_sc) = refs
    else:
        (table_ref, nblk_ref, len_ref, q_ref, k_ref, v_ref,
         acc_ref, m_ref, l_ref, acc_sc, m_sc, l_sc) = refs
    b, j = pl.program_id(0), pl.program_id(1)
    # the table's block this step reads: with bounds, the blocks wholly
    # before every row's first visible key are skipped
    blk = j + start_ref[b] if bounded else j

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, PAGED_NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(blk < nblk_ref[b])
    def _():
        col = blk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        ok = col < len_ref[b]
        if bounded:
            ok = ok & (col >= lo_ref[0][:, :1])               # [RG, bs]
        for h in range(n_kv):
            qh = q_ref[0, h]                                  # [RG, d]
            kh = k_ref[0, :, h * d:(h + 1) * d]               # [bs, d]
            vh = v_ref[0, :, h * d:(h + 1) * d]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [RG, bs]
            s = jnp.where(ok, s, PAGED_NEG)
            m_prev = m_sc[h]                                  # [RG, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new[:, :1]), 0.0)
            l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = acc_sc[h] * alpha[:, :1] + jnp.dot(
                p.astype(vh.dtype), vh, preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        acc_ref[0] = acc_sc[...]
        m_ref[0] = m_sc[...]
        l_ref[0] = l_sc[...]


def paged_gqa_attention(q, pool_k, pool_v, table, length, *, scale: float,
                        base=None, first=None, interpret: bool = False):
    """Attention of a FEW query rows over a long per-sequence cache
    that lives in blocks of a shared pool, read WHERE IT LIES: ``q [B,
    KV, RG, d]`` (for each of the ``KV`` key/value heads the ``RG``
    query rows that read it: token rows x the query heads of its
    group), ``pool_k`` / ``pool_v`` ``[blocks, bs, KV * d]``, ``table
    [B, nb]`` int32 (a sequence's blocks in order), ``length [B]`` (its
    cached rows: every one visible to every query row). Returns the
    UNNORMALISED parts of an online softmax, float32: ``acc [B, KV,
    RG, d]`` (``sum_s exp(score - m) v``), ``m [B, KV, RG]`` (the
    running maximum, ``PAGED_NEG`` for an empty cache) and ``l``
    (``sum_s exp(score - m)``), so that the caller can join them with
    the keys that are not in the cache (the block being decoded).

    With ``first [B, RG]`` the rows differ in their FIRST visible key
    (a sliding window): query row ``r`` sees the cached positions
    ``first[b, r] <= position < length[b]``, ``base [B]`` being the
    position of the table's first row (a multiple of ``bs``; None: 0:
    a window layer's table starts at the oldest block the session
    still holds). Rows before a row's bound are masked inside the
    kernel, and the blocks that lie wholly before every row's bound
    are never fetched. Without ``first`` the call, and the kernel it
    compiles to, are as they were.

    A Pallas TPU kernel: grid ``(B, nb)``; the block table, the
    sequences' block counts and lengths are prefetched scalars, so
    block ``j`` of sequence ``b`` is DMA'd straight from pool block
    ``table[b, j]`` (a step past the sequence's last block names that
    block again: nothing is fetched, nothing computed). No ``[B, S,
    ...]`` copy of the cache exists; bytes read are the cached rows'
    own. ``d`` a multiple of 128 lanes. ``RG`` is padded to whole
    sublane tiles here (7 query heads a key/value head make 7 x token
    rows); the pad rows see nothing and are cut off the result."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, RG, d = q.shape
    bs = pool_k.shape[1]
    nb = table.shape[1]
    bounded = first is not None
    length = length.astype(jnp.int32)
    sub = 8 * max(1, 4 // jnp.dtype(q.dtype).itemsize)
    pad = -RG % sub
    RP = RG + pad
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    def q_map(b, j, *_):
        return (b, 0, 0, 0)

    scalars = [table.astype(jnp.int32), None, length]
    operands = [q]
    in_specs = [pl.BlockSpec((1, KV, RP, d), q_map)]
    if bounded:
        # everything relative to the table's first row
        if base is not None:
            length = length - base.astype(jnp.int32)
            first = first - base.astype(jnp.int32)[:, None]
        lo = jnp.maximum(first.astype(jnp.int32), 0)
        start = jnp.min(lo, axis=1) // bs
        # a pad row's bound lies past every key
        lo = jnp.pad(lo, ((0, 0), (0, pad)),
                     constant_values=jnp.iinfo(jnp.int32).max)
        scalars = [scalars[0], None, length, start]
        operands.append(jnp.broadcast_to(lo[:, :, None], (B, RP, 128)))
        in_specs.append(pl.BlockSpec((1, RP, 128),
                                     lambda b, j, *_: (b, 0, 0)))
    scalars[1] = nblk = (length + bs - 1) // bs

    def kv_map(b, j, table_ref, nblk_ref, len_ref, *start_ref):
        last = jnp.maximum(nblk_ref[b] - 1, 0)
        at = j + start_ref[0][b] if start_ref else j
        return (table_ref[b, jnp.minimum(at, last)], 0, 0)

    out_shape = (jax.ShapeDtypeStruct((B, KV, RP, d), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, RP, 128), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, RP, 128), jnp.float32))
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_gqa_kernel, bs=bs, n_kv=KV, d=d,
                          scale=float(scale), bounded=bounded),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(B, nb),
            in_specs=in_specs + [pl.BlockSpec((1, bs, KV * d), kv_map),
                                 pl.BlockSpec((1, bs, KV * d), kv_map)],
            out_specs=[pl.BlockSpec((1, KV, RP, d), q_map),
                       pl.BlockSpec((1, KV, RP, 128), q_map),
                       pl.BlockSpec((1, KV, RP, 128), q_map)],
            scratch_shapes=[pltpu.VMEM((KV, RP, d), jnp.float32),
                            pltpu.VMEM((KV, RP, 128), jnp.float32),
                            pltpu.VMEM((KV, RP, 128), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="paged_gqa_attention",
    )(*scalars, *operands, pool_k, pool_v)
    return acc[:, :, :RG], m[:, :, :RG, 0], l[:, :, :RG, 0]
