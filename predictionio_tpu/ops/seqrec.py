"""Sequential-recommendation ops: a SASRec-style next-item encoder on
the attention kernels (ROADMAP item 1 — the first workload that consumes
``ops/attention.py``).

The model is a small causal transformer over each user's time-ordered
item sequence (Kang & McAuley's SASRec shape, the TurboGR /
generative-recommendation direction from PAPERS.md):

- learned item + position embeddings (tied item table: the same ``[M,
  D]`` matrix embeds inputs AND scores the output softmax — so a trained
  model serves through the standard factor-store top-k path: user vector
  = the encoder's hidden state at the last real position, item vectors =
  the embedding table, score = dot product);
- N pre-LN blocks of multi-head CAUSAL self-attention
  (:func:`~predictionio_tpu.ops.attention.mha_reference` with the
  key-padding mask — ragged histories batch into padded tables without
  attending pad rows) + a pointwise FFN;
- trained by one jitted ``lax.scan`` over optimizer steps (Adam,
  sampled-softmax over the item vocabulary: the full [B, L, M] logits
  never materialize);
- sequences are grouped into POWER-OF-TWO length buckets (the
  ``ops/als.PAD_MULTIPLE`` discipline): each bucket is one static-shape
  program, so a catalog of ragged histories compiles a handful of
  programs instead of one per distinct length.

Mesh lane: when a mesh is present the per-layer attention runs the
sequence-parallel kernels (``ring_attention`` / ``ulysses_attention``)
instead of the dense oracle — Ulysses when the head count divides the
mesh axis, the ring otherwise. The bucketed lengths are powers of two,
so divisibility by a 2^k mesh axis holds whenever L >= axis size.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core.base import Params
from predictionio_tpu.ops.als import PAD_MULTIPLE


@dataclasses.dataclass(frozen=True)
class SeqRecParams(Params):
    """The sequence lane's hyperparameters: the job (steps, batch,
    learning rate, negatives) and the BLOCK, which is data (ROADMAP
    Design 6). The defaults are the SASRec block; ``OLMOE_1B_7B`` below
    holds the values of the OLMoE-1B-7B block.

    ``rank`` is the model width AND the width of the user / item
    vectors, so a trained model drops into the same ``[N, R] x [M, R]``
    serving stores ALS uses. ``sp_mode`` selects the sequence-parallel
    attention lane when a mesh is present: ``auto`` (ulysses when heads
    divide the mesh axis, ring otherwise), ``ring``, ``ulysses``, or
    ``off`` (dense attention even on a mesh).

    The block: ``block`` names the layer function (``sasrec``: pre-norm
    MHA + biased ReLU FFN, inputs scaled by sqrt(width); ``olmoe``:
    pre-norm MHA with QK-norm + sparse SiLU-gated experts, no bias);
    ``norm`` (``layernorm`` | ``rmsnorm``), ``positions`` (``learned``
    | ``rope``) and ``tied`` are chosen apart from the ``sasrec`` block;
    the ``olmoe`` block takes them as OLMoE publishes them (rmsnorm,
    rope, untied) and refuses another choice; ``head_dim`` 0
    means ``rank / n_heads``; ``tied`` False gives the model a separate
    OUTPUT table (``out_emb``), which is then what the loss scores
    against and what serving holds as item vectors; ``vocab_rows`` is
    the rows both tables hold (0: the catalog's size; more rows than
    items are rows no id reaches, as a published vocabulary larger than
    the catalog). ``compute_dtype`` is the dtype matmul operands are
    cast to (products accumulate in float32; parameters, Adam's
    moments, norms, the router and the residual stream stay float32).
    Attention is the blocked Pallas kernel on a TPU for rows of 512
    and longer with whole 128-lane heads, else the dense form.

    The job: a step is ``batch_size`` rows, cut into microbatches of
    ``micro_rows`` rows (0: one) whose gradients are summed before
    Adam; ``encode_rows`` rows go through one encode call of a packed
    layout. ``lb_coef`` / ``z_coef`` weigh the experts' load-balancing
    loss and the router's z-loss."""

    rank: int = 32
    n_layers: int = 2
    n_heads: int = 2
    max_seq_len: int = 32
    num_steps: int = 300
    batch_size: int = 128
    learning_rate: float = 1e-3
    n_negatives: int = 64
    ffn_mult: int = 2
    l2: float = 0.0
    seed: int = 0
    sp_mode: str = "auto"
    block: str = "sasrec"
    head_dim: int = 0
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    positions: str = "learned"
    rope_theta: float = 10000.0
    tied: bool = True
    vocab_rows: int = 0
    n_experts: int = 0
    expert_width: int = 0
    experts_per_token: int = 0
    lb_coef: float = 0.01
    z_coef: float = 0.001
    compute_dtype: str = "float32"
    micro_rows: int = 0
    encode_rows: int = 8
    # the glm_moe_dsa block (ops/mla.py), under config.json's names
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    n_dense_layers: int = 0
    dense_width: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: int = 0
    expert_share: int = 0
    # the sdar_moe block (ops/sdar.py): key/value heads shared by
    # groups of query heads, the router's weights divided by their sum
    # (``norm_topk_prob``), and generation by diffusion over blocks: a
    # slate is decoded ``block_length`` positions at a time in passes
    # that unmask by ``remasking`` (``low_confidence_static``: the
    # ``ceil(masked / denoising_steps)`` most confident a pass;
    # ``low_confidence_dynamic``: every position at
    # ``confidence_threshold`` or above, at least one); ``mask_token``
    # is the mask token's row of the tables (negative: a last row the
    # tables get for it)
    n_kv_heads: int = 0
    norm_topk_prob: bool = False
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token: int = -1
    # the smallthinker block (ops/smallthinker.py), under config.json's
    # names: a layer ``i`` with ``sliding_window_layout[i]`` 1 is rotated
    # and attends the ``sliding_window_size`` newest positions, one with
    # 0 has no positions and attends all (the published ``rope_layout``
    # is the same list); ``max_seq_len`` is the positions a served
    # session may hold (``max_position_embeddings``)
    sliding_window_size: int = 0
    sliding_window_layout: Tuple[int, ...] = ()
    # the qwen3_next block (ops/qwen3next.py), under config.json's names
    # (linear_num_key_heads, linear_num_value_heads, linear_key_head_dim,
    # linear_value_head_dim, linear_conv_kernel_dim,
    # full_attention_interval, partial_rotary_factor,
    # shared_expert_intermediate_size): layer ``i`` is gated attention
    # where ``(i + 1) % full_attention_interval == 0`` and a Gated
    # DeltaNet layer otherwise; ``experts_held`` / ``expert_share`` say
    # which of the router's ``n_experts`` outputs this chip holds
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    full_attention_interval: int = 0
    partial_rotary_factor: float = 1.0
    shared_expert_width: int = 0
    # the falcon_h1 block (ops/falconh1.py), under config.json's names:
    # every layer runs ``mamba_n_heads`` Mamba-2 heads of
    # ``mamba_d_head`` (state ``mamba_d_state``, B and C in
    # ``mamba_n_groups`` groups, a convolution of ``mamba_d_conv``, the
    # chunked form in chunks of ``mamba_chunk_size``; the gated norm
    # AFTER the gate: ``mamba_norm_before_gate`` false, the one form
    # published) BESIDE its attention heads, then a dense SwiGLU of
    # ``intermediate_size``;
    # the muP multipliers scale what their names say
    # (``ssm_multipliers``: the z, x, B, C and dt slices of the input
    # projection; ``mlp_multipliers``: the gate's input, the output)
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 0
    mamba_d_conv: int = 0
    mamba_chunk_size: int = 0
    intermediate_size: int = 0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    # the session lane that serves these blocks (ops/sessions.py): the
    # cache pool's rows (0: twice the stored histories) and how many
    # dispatches' audits it keeps for a check to read (0: the
    # programs compute none)
    session_pool_tokens: int = 0
    session_audit: int = 0
    # serve the block's SEEDED INITIAL weights, drawn on the device at
    # deploy from ``seed``, instead of trained ones: what a benchmark
    # or a smoke test of a backbone too large to train here deploys.
    # ``num_steps`` 0 is refused without it.
    seeded_weights: bool = False


# the block of OLMoE-1B-7B-0125-Instruct as its config.json publishes
# it (https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct):
# SeqRecParams(**OLMOE_1B_7B, n_layers=..., ...)
OLMOE_1B_7B = dict(
    block="olmoe", rank=2048, n_heads=16, head_dim=128, norm="rmsnorm",
    norm_eps=1e-5, positions="rope", rope_theta=10000.0, tied=False,
    vocab_rows=50304, n_experts=64, expert_width=1024,
    experts_per_token=8)

# the block of GLM-5 (https://huggingface.co/zai-org/GLM-5, model_type
# glm_moe_dsa) as its config.json publishes it; ``n_layers``,
# ``n_dense_layers`` (first_k_dense_replace: 3 of the 78), the experts
# a chip holds (``experts_held`` of the 256, share ``expert_share``)
# and the rows of the tables are the deployment's
GLM_5 = dict(
    block="glm_moe_dsa", rank=6144, n_heads=64, norm="rmsnorm",
    norm_eps=1e-5, positions="rope", rope_theta=1000000.0, tied=False,
    q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32,
    index_head_dim=128, index_topk=2048, dense_width=12288,
    n_experts=256, expert_width=2048, experts_per_token=8,
    n_shared_experts=1, routed_scaling_factor=2.5)


# the block of SDAR-30B-A3B-Chat
# (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, model_type sdar_moe)
# as its config.json publishes it; ``n_layers`` and the generation
# settings (block_length, denoising_steps, remasking) are the
# deployment's
SDAR_30B_A3B = dict(
    block="sdar_moe", rank=2048, n_heads=32, n_kv_heads=4, head_dim=128,
    norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1000000.0,
    tied=False, vocab_rows=151936, n_experts=128, expert_width=768,
    experts_per_token=8, norm_topk_prob=True, mask_token=151669)

# the block of SmallThinker-21BA3B-Instruct
# (https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct,
# model_name smallthinker_21b_instruct) as its config.json publishes
# it; ``n_layers`` is the deployment's (the layout's period is 4)
SMALLTHINKER_21B_A3B = dict(
    block="smallthinker", rank=2560, n_heads=28, n_kv_heads=4,
    head_dim=128, norm="rmsnorm", norm_eps=1e-6, positions="rope",
    rope_theta=1500000.0, tied=False, vocab_rows=151936, n_experts=64,
    expert_width=768, experts_per_token=6, norm_topk_prob=True,
    sliding_window_size=4096, sliding_window_layout=(0, 1, 1, 1) * 13,
    max_seq_len=16384)


# the block of Qwen3-Next-80B-A3B-Instruct
# (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, model_type
# qwen3_next) as its config.json publishes it; ``n_layers``, the experts
# a chip holds (``experts_held`` of the 512, share ``expert_share``) and
# the rows of the tables are the deployment's
QWEN3_NEXT_80B_A3B = dict(
    block="qwen3_next", rank=2048, n_heads=16, n_kv_heads=2, head_dim=256,
    norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=10000000.0,
    partial_rotary_factor=0.25, tied=False, vocab_rows=151936,
    n_experts=512, expert_width=512, experts_per_token=10,
    norm_topk_prob=True, shared_expert_width=512, linear_key_heads=16,
    linear_value_heads=32, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel=4,
    full_attention_interval=4)


# the block of Falcon-H1-34B-Instruct
# (https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct, model_type
# falcon_h1) as its config.json publishes it; ``n_layers`` and the rows
# of the tables are the deployment's
FALCON_H1_34B = dict(
    block="falcon_h1", rank=5120, n_heads=20, n_kv_heads=4, head_dim=128,
    norm="rmsnorm", norm_eps=1e-5, positions="rope", rope_theta=1e11,
    tied=False, vocab_rows=261120, intermediate_size=21504,
    mamba_n_heads=32, mamba_d_head=128, mamba_d_state=256,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=128,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    ssm_in_multiplier=0.25,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What of :class:`SeqRecParams` shapes the compiled programs (the
    static argument of the jitted step and encode): the block, not the
    job's seed, step count or learning rate."""

    block: str
    n_layers: int
    n_heads: int
    head_dim: int
    norm: str
    norm_eps: float
    positions: str
    rope_theta: float
    tied: bool
    n_experts: int
    experts_per_token: int
    lb_coef: float
    z_coef: float
    compute_dtype: str
    glm: Any = None   # ops/mla.py::GlmSpec of the glm_moe_dsa block
    sdar: Any = None  # ops/sdar.py::SdarSpec of the sdar_moe block
    swa: Any = None   # ops/smallthinker.py::SwaSpec of the smallthinker block
    lin: Any = None   # ops/qwen3next.py::LinSpec of the qwen3_next block
    hyb: Any = None   # ops/falconh1.py::HybSpec of the falcon_h1 block

    @property
    def sparse(self) -> bool:
        return self.block == "olmoe"


def block_spec(params: SeqRecParams) -> BlockSpec:
    D, H = int(params.rank), int(params.n_heads)
    if params.block not in BLOCKS:
        raise ValueError(f"unknown block {params.block!r}; known: "
                         f"{sorted(BLOCKS)}")
    if params.norm not in ("layernorm", "rmsnorm"):
        raise ValueError(f"unknown norm {params.norm!r}")
    if params.positions not in ("learned", "rope"):
        raise ValueError(f"unknown positions {params.positions!r}")
    if params.compute_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"compute_dtype {params.compute_dtype!r}: float32 or bfloat16")
    if not params.head_dim and D % H:
        raise ValueError(f"rank {D} not divisible by n_heads {H}")
    head_dim = int(params.head_dim) or D // H
    if params.positions == "rope" and head_dim % 2:
        raise ValueError(f"rope needs an even head_dim, got {head_dim}")
    sparse = params.block == "olmoe"
    if sparse and not (0 < int(params.experts_per_token)
                       <= int(params.n_experts)
                       and int(params.expert_width) > 0):
        raise ValueError(
            "the olmoe block needs n_experts, expert_width and "
            "experts_per_token (0 < per token <= experts)")
    if sparse and (params.norm, params.positions, bool(params.tied)) != (
            "rmsnorm", "rope", False):
        # the one combination the reference and the tests hold it to
        raise ValueError(
            "the olmoe block takes norm rmsnorm, positions rope and "
            "untied tables (tied false), as OLMoE publishes it")
    glm = None
    if params.block == "glm_moe_dsa":
        from predictionio_tpu.ops import mla

        glm = mla.glm_spec(params)
    sdar = None
    if params.block == "sdar_moe":
        from predictionio_tpu.ops import sdar as _sdar

        sdar = _sdar.sdar_spec(params)
    swa = None
    if params.block == "smallthinker":
        from predictionio_tpu.ops import smallthinker

        swa = smallthinker.swa_spec(params)
    lin = None
    if params.block == "qwen3_next":
        from predictionio_tpu.ops import qwen3next

        lin = qwen3next.lin_spec(params)
    hyb = None
    if params.block == "falcon_h1":
        from predictionio_tpu.ops import falconh1

        hyb = falconh1.hyb_spec(params)
    return BlockSpec(
        params.block, int(params.n_layers), H, head_dim, params.norm,
        float(params.norm_eps), params.positions,
        float(params.rope_theta), bool(params.tied),
        int(params.n_experts) if sparse else 0,
        int(params.experts_per_token) if sparse else 0,
        float(params.lb_coef), float(params.z_coef),
        params.compute_dtype, glm, sdar, swa, lin, hyb)


@dataclasses.dataclass
class SequenceBucket:
    """One static-shape batch of same-length-class sequences.

    ``rows[i]`` is the ORIGINAL row index (user index) of padded row i;
    ``ids`` are item indices (0-padded — pad slots are masked, never
    attended or scored); ``mask`` is 1.0 on real positions.

    The encoder and the trainer read a bucket through the names a
    packed layout has too: ``seg`` (segment ids, 0 = pad: one segment
    per row here), ``pos`` (positions inside the segment), ``users``
    and ``last`` (each segment's user and the flat index of its last
    token)."""

    rows: np.ndarray   # int64 [B]
    ids: np.ndarray    # int32 [B, L]
    mask: np.ndarray   # float32 [B, L]

    @property
    def seq_len(self) -> int:
        return int(self.ids.shape[1])

    def __len__(self) -> int:
        return int(self.ids.shape[0])

    @property
    def seg(self) -> np.ndarray:
        return (np.asarray(self.mask) > 0).astype(np.int32)

    @property
    def pos(self) -> np.ndarray:
        return np.broadcast_to(np.arange(self.seq_len, dtype=np.int32),
                               self.ids.shape)

    @property
    def users(self) -> np.ndarray:
        return np.asarray(self.rows, dtype=np.int64)

    @property
    def last(self) -> np.ndarray:
        """Flat index of each row's last real token (-1: an empty
        row, whose vector stays zero)."""
        lens = np.asarray(self.mask).sum(axis=1).astype(np.int64)
        flat = np.arange(len(self), dtype=np.int64) * self.seq_len \
            + lens - 1
        return np.where(lens > 0, flat, -1)


@dataclasses.dataclass
class PackedRows:
    """Ragged histories packed first-fit into rows of one length, so
    that a row of 4,096 slots holds some seventy histories of mean
    length 59 instead of one padded to a power of two. ``seg`` numbers
    the histories of a row from 1 (0 = the row's unused tail): attention
    stays inside a segment and no target crosses a boundary; ``pos``
    restarts at 0 in every segment."""

    ids: np.ndarray     # int32 [R, L]
    seg: np.ndarray     # int32 [R, L]
    pos: np.ndarray     # int32 [R, L]
    users: np.ndarray   # int64 [S]: original row (user) of each segment
    last: np.ndarray    # int64 [S]: flat index of its last token

    @property
    def seq_len(self) -> int:
        return int(self.ids.shape[1])

    def __len__(self) -> int:
        return int(self.ids.shape[0])

    @property
    def n_tokens(self) -> int:
        return int(np.count_nonzero(self.seg))

    @property
    def pad_share(self) -> float:
        return 1.0 - self.n_tokens / max(1, self.seg.size)


def length_bucket(n: int, lo: int = PAD_MULTIPLE) -> int:
    """The power-of-two length class ``n`` pads to (min ``lo`` — the
    same pad discipline as the ALS tables: ``ops/als.PAD_MULTIPLE``).
    One ladder definition: delegates to the serving bucket rounder so
    train-time length classes and serve-time shape buckets can never
    diverge."""
    from predictionio_tpu.ops.serving import bucket_size

    return bucket_size(n, lo)


def bucket_sequences(seqs: Sequence[np.ndarray],
                     max_len: Optional[int] = None) -> List[SequenceBucket]:
    """Group ragged per-user item sequences into power-of-two length
    buckets. Sequences longer than ``max_len`` keep their LAST
    ``max_len`` items (the most recent history is the signal — same
    keep-the-informative-suffix convention SASRec trains with). Empty
    sequences are dropped (their rows simply appear in no bucket).
    Buckets come back shortest class first."""
    by_len: Dict[int, List[Tuple[int, np.ndarray]]] = {}
    for row, seq in enumerate(seqs):
        seq = np.asarray(seq, dtype=np.int32)
        if max_len is not None and len(seq) > max_len:
            seq = seq[-int(max_len):]
        if not len(seq):
            continue
        by_len.setdefault(length_bucket(len(seq)), []).append((row, seq))
    buckets: List[SequenceBucket] = []
    for L in sorted(by_len):
        members = by_len[L]
        B = len(members)
        ids = np.zeros((B, L), dtype=np.int32)
        mask = np.zeros((B, L), dtype=np.float32)
        rows = np.empty(B, dtype=np.int64)
        for i, (row, seq) in enumerate(members):
            rows[i] = row
            ids[i, :len(seq)] = seq
            mask[i, :len(seq)] = 1.0
        buckets.append(SequenceBucket(rows, ids, mask))
    return buckets


def pack_sequences(seqs: Sequence[np.ndarray], row_len: int) -> PackedRows:
    """Pack ragged sequences FIRST-FIT into rows of ``row_len`` slots:
    each sequence, in the order given, goes whole into the first row
    that still has room for it (a new row when none has). Sequences
    longer than a row keep their last ``row_len`` items; empty ones are
    dropped. Every row holds tokens (an encode call pads its own last
    batch: :func:`_encode_batches`)."""
    row_len = int(row_len)
    lens = np.fromiter((min(len(s), row_len) for s in seqs),
                       dtype=np.int64, count=len(seqs))
    free = np.empty(0, dtype=np.int64)     # slots left in each open row
    row_of = np.full(len(seqs), -1, dtype=np.int64)
    start_of = np.zeros(len(seqs), dtype=np.int64)
    first_open = 0                         # rows before it are full
    for i, n in enumerate(lens.tolist()):
        if not n:
            continue
        fits = np.flatnonzero(free[first_open:] >= n)
        if len(fits):
            r = first_open + int(fits[0])
        else:
            r = len(free)
            free = np.append(free, row_len)
        row_of[i], start_of[i] = r, row_len - free[r]
        free[r] -= n
        while first_open < len(free) and free[first_open] == 0:
            first_open += 1
    R = max(len(free), 1)
    ids = np.zeros((R, row_len), dtype=np.int32)
    seg = np.zeros((R, row_len), dtype=np.int32)
    pos = np.zeros((R, row_len), dtype=np.int32)
    next_seg = np.zeros(R, dtype=np.int32)
    users = np.flatnonzero(row_of >= 0)
    last = np.empty(len(users), dtype=np.int64)
    for j, i in enumerate(users.tolist()):
        r, a, n = int(row_of[i]), int(start_of[i]), int(lens[i])
        next_seg[r] += 1
        ids[r, a:a + n] = np.asarray(seqs[i])[-n:]
        seg[r, a:a + n] = next_seg[r]
        pos[r, a:a + n] = np.arange(n)
        last[j] = r * row_len + a + n - 1
    return PackedRows(ids, seg, pos, users.astype(np.int64), last)


# ---------------------------------------------------------------------------
# Parameters / forward pass
# ---------------------------------------------------------------------------

def table_rows(n_items: int, params: SeqRecParams) -> int:
    """Rows of the item table(s): ``vocab_rows`` when the block
    publishes a vocabulary larger than the catalog; the ``sdar_moe``
    block's tables hold its mask token's row too (one past the catalog
    when ``mask_token`` names none)."""
    rows = max(int(n_items), int(params.vocab_rows))
    if params.block == "sdar_moe":
        rows = max(rows, int(params.mask_token) + 1) \
            if int(params.mask_token) >= 0 else max(rows, int(n_items) + 1)
    return rows


def init_theta(n_items: int, params: SeqRecParams) -> Dict[str, np.ndarray]:
    """Initialize the encoder parameter pytree (host numpy — pickles
    into the Models repo like any P2L model; device copies are made per
    call and cached by jit). :func:`init_theta_device` draws the same
    values and leaves them on the device."""
    return {k: np.asarray(v, dtype=np.float32)
            for k, v in init_theta_device(n_items, params).items()}


def _theta_shapes(n_items: int, params: SeqRecParams
                  ) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(name, shape, init) of every parameter, in drawing order.
    ``init`` is ``("div", x)`` / ``("mul", x)`` for a normal draw
    divided / multiplied by ``x``, or the constant a norm's gain or a
    bias starts from."""
    spec = block_spec(params)
    D, V = int(params.rank), table_rows(n_items, params)
    if spec.glm is not None:
        from predictionio_tpu.ops import mla

        return mla.theta_shapes(V, spec.glm)
    if spec.sdar is not None:
        from predictionio_tpu.ops import sdar as _sdar

        return _sdar.theta_shapes(V, spec.sdar)
    if spec.swa is not None:
        from predictionio_tpu.ops import smallthinker

        return smallthinker.theta_shapes(V, spec.swa)
    if spec.lin is not None:
        from predictionio_tpu.ops import qwen3next

        return qwen3next.theta_shapes(V, spec.lin)
    if spec.hyb is not None:
        from predictionio_tpu.ops import falconh1

        return falconh1.theta_shapes(V, spec.hyb)
    A = spec.n_heads * spec.head_dim
    out: List[Tuple[str, Tuple[int, ...], Any]] = [
        ("item_emb", (V, D), ("div", math.sqrt(D)))]
    if spec.positions == "learned":
        out.append(("pos_emb", (length_bucket(int(params.max_seq_len)), D),
                    ("mul", 0.01)))
    bias = spec.norm == "layernorm"
    out.append(("ln_f_g", (D,), 1.0))
    if bias:
        out.append(("ln_f_b", (D,), 0.0))
    for i in range(spec.n_layers):
        for name, shape in (("wq", (D, A)), ("wk", (D, A)),
                            ("wv", (D, A)), ("wo", (A, D))):
            out.append((f"l{i}_{name}", shape,
                        ("div", math.sqrt(shape[0]))))
        if spec.sparse:
            E, F = spec.n_experts, int(params.expert_width)
            out += [
                (f"l{i}_router", (D, E), ("div", math.sqrt(D))),
                (f"l{i}_we_gate", (E, D, F), ("div", math.sqrt(D))),
                (f"l{i}_we_up", (E, D, F), ("div", math.sqrt(D))),
                (f"l{i}_we_down", (E, F, D), ("div", math.sqrt(F))),
                (f"l{i}_qn_g", (A,), 1.0), (f"l{i}_kn_g", (A,), 1.0)]
        else:
            F = D * int(params.ffn_mult)
            out += [(f"l{i}_w1", (D, F), ("div", math.sqrt(D))),
                    (f"l{i}_w2", (F, D), ("div", math.sqrt(F))),
                    (f"l{i}_b1", (F,), 0.0), (f"l{i}_b2", (D,), 0.0)]
        for ln in ("ln1", "ln2"):
            out.append((f"l{i}_{ln}_g", (D,), 1.0))
            if bias:
                out.append((f"l{i}_{ln}_b", (D,), 0.0))
    if not spec.tied:
        out.append(("out_emb", (V, D), ("div", math.sqrt(D))))
    return out


def init_theta_device(n_items: int, params: SeqRecParams):
    """Every parameter drawn on the device, op by op (the SASRec
    defaults draw, key for key and bit for bit, what earlier versions
    drew: keys 0 and 1 for the two tables, then six a layer out of
    ``2 + 8 * n_layers``)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.mla import draw_value

    shapes = _theta_shapes(n_items, params)
    drawn = sum(isinstance(s[2], tuple) for s in shapes)
    keys = jax.random.split(jax.random.PRNGKey(int(params.seed)),
                            max(drawn, 2 + 8 * int(params.n_layers)))
    theta = {}
    kx = 0
    for name, shape, init in shapes:
        if isinstance(init, tuple):
            theta[name] = draw_value(keys[kx], shape, init).astype(
                jnp.float32)
            kx += 1
        else:
            theta[name] = jnp.full(shape, init, jnp.float32)
    return theta


def _mm(a, w, spec: BlockSpec, w_low=None):
    """``a @ w`` with the operands in the compute dtype and the product
    accumulated in float32 (float32 compute: the plain product, as the
    SASRec block has always taken it). ``w_low``: ``w`` already in the
    compute dtype (:func:`low_precision_copies`)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.moe import use_low

    if spec.compute_dtype == "float32":
        return a @ w
    cd = jnp.dtype(spec.compute_dtype)
    return jnp.matmul(a.astype(cd), use_low(w, w_low, cd),
                      preferred_element_type=jnp.float32)


_LOW_NAMES = ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down")


def low_precision_copies(theta, spec: BlockSpec):
    """The layers' matmul weights in the compute dtype, made ONCE for
    the many forward passes that share them (a step's microbatches, an
    encode's calls) instead of once a pass; empty at float32."""
    import jax.numpy as jnp

    if spec.compute_dtype == "float32":
        return {}
    cd = jnp.dtype(spec.compute_dtype)
    return {k: v.astype(cd) for k, v in theta.items()
            if k.split("_", 1)[-1] in _LOW_NAMES}


def _layer_norm(x, g, b, eps: float = 1e-6):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rms_norm(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _norm(theta, name: str, x, spec: BlockSpec):
    if spec.norm == "rmsnorm":
        # (the qwen3_next block's norms are zero-centred)
        g = theta[f"{name}_g"]
        return _rms_norm(x, g if spec.lin is None else 1.0 + g,
                         spec.norm_eps)
    return _layer_norm(x, theta[f"{name}_g"], theta[f"{name}_b"],
                       spec.norm_eps)


def _heads_split(x, n_heads: int):
    # [B, L, D] -> [B, H, L, D/H]
    B, L, D = x.shape
    return x.reshape(B, L, n_heads, D // n_heads).transpose(0, 2, 1, 3)


def _heads_join(x):
    # [B, H, L, Dh] -> [B, L, D]
    B, H, L, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, L, H * Dh)


def _rope(x, pos, theta: float):
    """Rotary positions on ``x: [B, H, L, Dh]`` at ``pos: [B, L]``,
    the half-split convention of the OLMo / Llama family's public
    code: ``x * cos + rotate_half(x) * sin`` with the frequencies
    repeated over the two halves."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def resolve_attention(spec: BlockSpec, seq_len: int) -> str:
    """``dense`` or ``flash`` for rows of ``seq_len``: the blocked
    kernel is the TPU's, and wants whole 128-lane heads."""
    import jax

    if jax.default_backend() == "tpu" and seq_len >= 512 \
            and spec.head_dim % 128 == 0:
        return "flash"
    return "dense"


def _segment_attention(q, k, v, seg, spec: BlockSpec):
    import jax.numpy as jnp

    from predictionio_tpu.ops import attention as _attention

    if resolve_attention(spec, q.shape[2]) == "dense":
        return _attention.segment_attention_dense(q, k, v, seg)
    cd = jnp.dtype(spec.compute_dtype)
    return _attention.segment_attention_flash(
        q.astype(cd), k.astype(cd), v.astype(cd), seg).astype(jnp.float32)


def _attention_block(theta, i: int, h, seg, pos, spec: BlockSpec,
                     attention_fn, low):
    """Projections, (QK-norm over the WHOLE projection, before the
    split into heads), positions, causal attention inside segments,
    output projection."""
    import jax

    q, k, v = (_mm(h, theta[f"l{i}_{w}"], spec, low.get(f"l{i}_{w}"))
               for w in ("wq", "wk", "wv"))
    if spec.sparse:
        with jax.named_scope("attn/qk_norm"):
            q = _rms_norm(q, theta[f"l{i}_qn_g"], spec.norm_eps)
            k = _rms_norm(k, theta[f"l{i}_kn_g"], spec.norm_eps)
    q, k, v = (_heads_split(x, spec.n_heads) for x in (q, k, v))
    if spec.positions == "rope":
        with jax.named_scope("attn/rope"):
            q = _rope(q, pos, spec.rope_theta)
            k = _rope(k, pos, spec.rope_theta)
    with jax.named_scope("attn/flash"):
        a = attention_fn(q, k, v, seg) if attention_fn is not None \
            else _segment_attention(q, k, v, seg, spec)
    return _mm(_heads_join(a), theta[f"l{i}_wo"], spec,
               low.get(f"l{i}_wo"))


def _sasrec_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                  attention_fn, low):
    """``x += Wo·MHA(norm(x))`` then ``x += FFN(norm(x))``, a biased
    ReLU FFN; pad positions held at zero."""
    import jax.numpy as jnp

    h = _norm(theta, f"l{i}_ln1", x, spec)
    x = x + _attention_block(theta, i, h, seg, pos, spec,
                             attention_fn, low) * keep
    h2 = _norm(theta, f"l{i}_ln2", x, spec)
    f = jnp.maximum(_mm(h2, theta[f"l{i}_w1"], spec)
                    + theta[f"l{i}_b1"], 0.0)
    x = x + (_mm(f, theta[f"l{i}_w2"], spec) + theta[f"l{i}_b2"]) * keep
    return x, None


def _olmoe_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                 attention_fn, low):
    """OLMoE's layer: ``x += Wo·MHA(rms(x))`` with QK-norm and rotary
    positions, then ``x += experts(rms(x))``: 8 of 64 SiLU-gated
    experts a token, router weights not renormalised."""
    from predictionio_tpu.ops import moe

    B, L, D = x.shape
    h = _norm(theta, f"l{i}_ln1", x, spec)
    x = x + _attention_block(theta, i, h, seg, pos, spec, attention_fn,
                             low)
    h2 = _norm(theta, f"l{i}_ln2", x, spec).reshape(B * L, D)
    y, stats = moe.moe_ffn(
        h2, theta[f"l{i}_router"], theta[f"l{i}_we_gate"],
        theta[f"l{i}_we_up"], theta[f"l{i}_we_down"],
        k=spec.experts_per_token, compute_dtype=spec.compute_dtype,
        valid=keep.reshape(B * L),
        low=tuple(low.get(f"l{i}_we_{w}") for w in ("gate", "up", "down")))
    return x + y.reshape(B, L, D), stats


def _glm_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
               attention_fn, low):
    """GLM-5's layer (``ops/mla.py``): latent attention under the
    indexer's cut, then the dense or the expert feed-forward."""
    from predictionio_tpu.ops import mla

    return mla.glm_layer(theta, i, x, seg, pos, spec.glm), None


def _sdar_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                attention_fn, low):
    """SDAR's layer (``ops/sdar.py``): grouped-query attention under
    the block-causal mask (a position sees its whole block and every
    earlier one of its segment), then the renormalised expert layer.
    An id that is the mask token's row is a masked position."""
    from predictionio_tpu.ops import sdar

    return sdar.sdar_layer(theta, i, x, seg, pos, spec.sdar), None


def _smallthinker_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                        attention_fn, low):
    """SmallThinker's layer (``ops/smallthinker.py``): grouped-query
    attention of the layer's kind (global without positions, or
    rotated inside a sliding window), then ReGLU experts routed from
    the attention's input."""
    from predictionio_tpu.ops import smallthinker

    return smallthinker.smallthinker_layer(theta, i, x, seg, pos,
                                           spec.swa), None


def _qwen3next_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                     attention_fn, low):
    """Qwen3-Next's layer (``ops/qwen3next.py``): gated attention every
    ``full_attention_interval``-th layer, a Gated DeltaNet layer (the
    chunked form from a zero state; ONE segment a row) otherwise, then
    the held routed experts beside the gated shared one."""
    from predictionio_tpu.ops import qwen3next

    return qwen3next.qwen3next_layer(theta, i, x, seg, pos, spec.lin), None


def _falconh1_layer(theta, i: int, x, seg, pos, keep, spec: BlockSpec,
                    attention_fn, low):
    """Falcon-H1's layer (``ops/falconh1.py``): attention heads and
    Mamba-2 heads (the chunked form from a zero state; ONE segment a
    row) side by side on one normed input, one residual add for both,
    then a dense SwiGLU."""
    from predictionio_tpu.ops import falconh1

    return falconh1.falconh1_layer(theta, i, x, seg, pos, spec.hyb), None


# one function per layer kind; ``SeqRecParams.block`` names one
BLOCKS = {"sasrec": _sasrec_layer, "olmoe": _olmoe_layer,
          "glm_moe_dsa": _glm_layer, "sdar_moe": _sdar_layer,
          "smallthinker": _smallthinker_layer,
          "qwen3_next": _qwen3next_layer, "falcon_h1": _falconh1_layer}


def encoder_forward(theta, ids, seg, pos=None, *, spec: BlockSpec,
                    attention_fn=None, low=None):
    """The encoder: ``[B, L]`` item ids -> ``([B, L, D]`` hidden states
    (float32, pad positions exactly zero), per-layer expert statistics
    (None for a dense layer)``)``.

    ``seg`` holds each position's segment id, 0 for padding (a padded
    bucket has one segment a row: its mask; a packed row has many);
    ``pos`` the position inside the segment (None: 0, 1, 2, ... along
    the row). Attention is causal and stays inside a segment.
    ``attention_fn(q, k, v, seg)`` replaces the block's own attention;
    the mesh lane passes the sequence-parallel kernels. ``low``:
    :func:`low_precision_copies` of ``theta``, where the caller has
    made them for several passes."""
    import jax.numpy as jnp

    B, L = ids.shape
    D = theta["item_emb"].shape[1]
    seg = jnp.asarray(seg)
    keep = (seg != 0).astype(jnp.float32)[..., None]
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = jnp.take(theta["item_emb"], ids, axis=0)
    if spec.block == "sasrec":
        x = x * math.sqrt(D)
    if spec.hyb is not None:
        x = x * spec.hyb.emb_mult
    if spec.positions == "learned":
        x = x + jnp.take(theta["pos_emb"], pos, axis=0)
    x = x * keep
    layer = BLOCKS[spec.block]
    stats = []
    for i in range(spec.n_layers):
        x, st = layer(theta, i, x, seg, pos, keep, spec, attention_fn,
                      low or {})
        stats.append(st)
    x = _norm(theta, "ln_f", x, spec)
    if spec.hyb is not None:
        # (so that a state scores against the output table as
        # published: ``W_head h * lm_head_multiplier``)
        x = x * spec.hyb.head_mult
    return x * keep, stats


def output_table(theta):
    """The table the loss scores against and serving holds as item
    vectors: the separate output table of an untied model, else the
    (tied) input table."""
    return theta["out_emb"] if "out_emb" in theta else theta["item_emb"]


def _take_last(h, last):
    """Hidden states at the flat positions ``last`` (``[S]``, -1 for
    none) -> ``[S, D]`` vectors, zero where there is none."""
    import jax.numpy as jnp

    flat = h.reshape(-1, h.shape[-1])
    vec = jnp.take(flat, jnp.maximum(last, 0), axis=0)
    return vec * (last >= 0)[:, None]


@functools.lru_cache(maxsize=16)
def _encode_jit(spec: BlockSpec):
    import jax

    def seq_encode(theta, ids, seg, pos, last):
        h, _ = encoder_forward(theta, ids, seg, pos, spec=spec)
        return _take_last(h, last)

    return jax.jit(seq_encode)


@functools.lru_cache(maxsize=16)
def _encode_into_jit(spec: BlockSpec):
    import jax

    def seq_encode(theta, low, U, ids, seg, pos, last, users):
        h, _ = encoder_forward(theta, ids, seg, pos, spec=spec, low=low)
        # a slot past the table (the padding of ``users``) is dropped
        return U.at[users].set(_take_last(h, last), mode="drop")

    return jax.jit(seq_encode, donate_argnums=2)


@functools.lru_cache(maxsize=16)
def _low_copies_jit(spec: BlockSpec):
    import jax

    def seq_low_copies(theta):
        return low_precision_copies(theta, spec)

    return jax.jit(seq_low_copies)


def _encode_bucket_device(theta, bucket, spec: BlockSpec):
    return _encode_jit(spec)(theta, bucket.ids, bucket.seg, bucket.pos,
                             bucket.last.astype(np.int32))


def encode_bucket(theta, bucket, params: SeqRecParams) -> np.ndarray:
    """One bucket's user vectors ``[B, D]`` (single-device jitted
    program, cached per block x shape)."""
    return np.asarray(_encode_bucket_device(theta, bucket,
                                            block_spec(params)),
                      dtype=np.float32)


def _encode_batches(layout, rows: int):
    """A packed layout cut into encode calls of ``rows`` rows: the
    slices of its arrays, and each call's segments (their flat
    positions inside the call, their users) padded to one count so
    that every call is one program."""
    L = layout.seq_len
    call_of = layout.last // (rows * L)
    n_calls = -(-len(layout) // rows)
    width = int(np.bincount(call_of, minlength=n_calls).max())
    width = -(-width // 128) * 128
    for c in range(n_calls):
        mine = np.flatnonzero(call_of == c)
        last = np.full(width, -1, dtype=np.int32)
        users = np.full(width, np.iinfo(np.int32).max, dtype=np.int32)
        last[:len(mine)] = layout.last[mine] - c * rows * L
        users[:len(mine)] = layout.users[mine]
        sl = slice(c * rows, (c + 1) * rows)
        parts = [layout.ids[sl], layout.seg[sl], layout.pos[sl]]
        short = rows - len(parts[0])
        if short:
            parts = [np.pad(p, ((0, short), (0, 0))) for p in parts]
        yield (*parts, last, users)


def encode_users(theta, buckets, n_users: int, params: SeqRecParams,
                 mesh=None, to_host: bool = True):
    """All users' vectors ``[n_users, D]`` — rows in no bucket (users
    with no events) stay zero. ``buckets`` is a list of padded buckets
    or one :class:`PackedRows`. The table is filled on the device, one
    encode call after another, and copied to the host once at the end
    (``to_host=False``: not at all). With a mesh the per-layer
    attention of a padded bucket runs the sequence-parallel kernels
    (:func:`encode_bucket_mesh`)."""
    import jax.numpy as jnp

    spec = block_spec(params)
    U = jnp.zeros((n_users, int(params.rank)), jnp.float32)
    if isinstance(buckets, PackedRows):
        run = _encode_into_jit(spec)
        low = _low_copies_jit(spec)(theta)
        for batch in _encode_batches(buckets, int(params.encode_rows)):
            U = run(theta, low, U, *batch)
    else:
        for bucket in buckets:
            if mesh is not None and params.sp_mode != "off":
                vecs = encode_bucket_mesh(theta, bucket, params, mesh,
                                          to_host=False)
            else:
                vecs = _encode_bucket_device(theta, bucket, spec)
            U = U.at[bucket.rows].set(vecs)
    return np.asarray(U, dtype=np.float32) if to_host else U


# ---------------------------------------------------------------------------
# Mesh lane: the sequence-parallel kernels, finally in anger
# ---------------------------------------------------------------------------

def select_sp_kernel(mesh, axis_name: str, n_heads: int, seq_len: int,
                     sp_mode: str = "auto") -> Optional[str]:
    """Which sequence-parallel kernel a (mesh, shape) pair can run:
    ``ulysses`` when both heads and length divide the axis, else
    ``ring`` when the length divides, else ``None`` (dense fallback —
    e.g. an 8-long bucket on an 8-way mesh leaves no tokens to shard).
    An explicit ``sp_mode`` forces its lane and raises when the shape
    cannot support it."""
    size = mesh.shape[axis_name]
    if sp_mode == "off":
        return None
    ring_ok = seq_len % size == 0 and seq_len >= 2 * size
    uly_ok = ring_ok and n_heads % size == 0
    if sp_mode == "ulysses":
        if not uly_ok:
            raise ValueError(
                f"sp_mode=ulysses needs heads ({n_heads}) and length "
                f"({seq_len}) divisible by the {size}-way mesh axis")
        return "ulysses"
    if sp_mode == "ring":
        if not ring_ok:
            raise ValueError(
                f"sp_mode=ring needs length ({seq_len}) divisible by "
                f"the {size}-way mesh axis")
        return "ring"
    if uly_ok:
        return "ulysses"
    if ring_ok:
        return "ring"
    return None


def encode_bucket_mesh(theta, bucket: SequenceBucket,
                       params: SeqRecParams, mesh,
                       axis_name: str = "data", to_host: bool = True):
    """Encode one bucket with the per-layer attention running
    SEQUENCE-PARALLEL over the mesh (ring or Ulysses — the kernels'
    first real workload). The non-attention math runs replicated jnp
    ops; the attention programs are the cached shard_map jits from
    ``ops/attention.py``. Falls back to the single-device program when
    the bucket's length class cannot shard over the axis."""
    from predictionio_tpu.ops.attention import (
        ring_attention,
        ulysses_attention,
    )

    kernel = select_sp_kernel(mesh, axis_name, int(params.n_heads),
                              bucket.seq_len, params.sp_mode)
    if kernel is None:
        out = _encode_bucket_device(theta, bucket, block_spec(params))
        return np.asarray(out, dtype=np.float32) if to_host else out
    sp = ring_attention if kernel == "ring" else ulysses_attention

    def attention_fn(q, k, v, seg):
        # one segment a row: the segment ids are the key-padding mask
        return sp(q, k, v, mesh, axis_name=axis_name, causal=True,
                  key_padding_mask=seg)

    import jax.numpy as jnp

    theta_d = {k: jnp.asarray(v) for k, v in theta.items()}
    h, _ = encoder_forward(theta_d, jnp.asarray(bucket.ids),
                           jnp.asarray(bucket.mask), None,
                           spec=block_spec(params),
                           attention_fn=attention_fn)
    out = _take_last(h, jnp.asarray(bucket.last.astype(np.int32)))
    return np.asarray(out, dtype=np.float32) if to_host else out


# ---------------------------------------------------------------------------
# Training: Adam steps over host-fed batches, sampled softmax over the vocab
# ---------------------------------------------------------------------------

def sampled_softmax_terms(theta, ids, seg, pos, negs, *, spec: BlockSpec,
                          low=None):
    """Next-item sampled softmax: position t's hidden state scores the
    TRUE next item ``ids[t+1]`` (of the same segment) against ``negs``
    shared negatives, on the OUTPUT table; the full [B, L, M] logits
    never materialize. Returns ``nll`` (summed negative
    log-likelihood), ``targets`` (their count), the layers' expert
    ``stats``, and what a comparison with the reference reads:
    ``hidden``, ``pos_logit`` ``[B, L-1]``, ``neg_logit`` ``[B, L-1,
    N]``."""
    import jax
    import jax.numpy as jnp

    h, stats = encoder_forward(theta, ids, seg, pos, spec=spec, low=low)
    with jax.named_scope("loss"):
        seg = jnp.asarray(seg)
        ctx = h[:, :-1, :]                            # [B, L-1, D]
        pos_ids = ids[:, 1:]                          # [B, L-1]
        valid = ((seg[:, :-1] == seg[:, 1:])
                 & (seg[:, :-1] != 0)).astype(jnp.float32)
        E = output_table(theta)
        pos_e = jnp.take(E, pos_ids, axis=0)          # [B, L-1, D]
        neg_e = jnp.take(E, negs, axis=0)             # [Nn, D]
        if spec.compute_dtype != "float32":
            cd = jnp.dtype(spec.compute_dtype)
            ctx, pos_e, neg_e = (x.astype(cd).astype(jnp.float32)
                                 for x in (ctx, pos_e, neg_e))
        pos_logit = jnp.sum(ctx * pos_e, axis=-1)     # [B, L-1]
        neg_logit = jnp.einsum("bld,nd->bln", ctx, neg_e)
        logits = jnp.concatenate([pos_logit[..., None], neg_logit],
                                 axis=-1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = (lse - pos_logit) * valid
        return {"nll": jnp.sum(nll), "targets": jnp.sum(valid),
                "stats": stats, "hidden": h, "pos_logit": pos_logit,
                "neg_logit": neg_logit}


def _micro_loss(theta, ids, seg, pos, negs, n_targets, n_micro: int, *,
                spec: BlockSpec, l2: float, low=None):
    """One microbatch's part of a step's loss: its summed NLL over the
    STEP's target count, and a ``1 / n_micro`` share of the penalties
    (the experts' auxiliary losses are means over this microbatch's
    tokens, as a device batch's are in OLMoE's recipe)."""
    import jax.numpy as jnp

    terms = sampled_softmax_terms(theta, ids, seg, pos, negs, spec=spec,
                                  low=low)
    stats = terms["stats"]
    loss = terms["nll"] / n_targets
    penalty = 0.0
    if l2:
        E = theta["item_emb"]
        penalty = l2 * jnp.sum(jnp.square(E)) / E.shape[0]
    for st in stats:
        if st is not None:
            penalty = penalty + spec.lb_coef * st["lb_loss"] \
                + spec.z_coef * st["z_loss"]
    if l2 or spec.sparse:
        loss = loss + penalty / n_micro
    load = [st["group_sizes"] for st in stats if st is not None]
    dropped = sum((st["dropped"] for st in stats if st is not None),
                  jnp.zeros((), jnp.int32))
    return loss, (jnp.stack(load) if load else None, dropped)


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def step_gradients(theta, ids, seg, pos, negs, *, spec: BlockSpec,
                   l2: float = 0.0):
    """One step's loss and float32 gradients: ``ids / seg / pos:
    [n_micro, rows, L]``, a scan over the microbatches whose gradients
    are summed as they come. Returns ``(loss, gradients, targets,
    per-layer per-expert pair counts or None, dropped pairs)``."""
    import jax
    import jax.numpy as jnp

    n_micro = ids.shape[0]
    # one cast of the weights for all the step's microbatches
    low = low_precision_copies(theta, spec) if n_micro > 1 else None
    grad_fn = jax.value_and_grad(
        functools.partial(_micro_loss, spec=spec, l2=l2, low=low),
        has_aux=True)
    n_targets = jnp.maximum(jnp.sum(
        (seg[:, :, :-1] == seg[:, :, 1:]) & (seg[:, :, :-1] != 0)
    ).astype(jnp.float32), 1.0)
    if n_micro == 1:
        (loss, (load, dropped)), g = grad_fn(
            theta, ids[0], seg[0], pos[0], negs, n_targets, 1)
        return loss, g, n_targets, load, dropped

    def body(acc, batch):
        (loss, (load, dropped)), g = grad_fn(theta, *batch, negs,
                                             n_targets, n_micro)
        return (jax.tree_util.tree_map(jnp.add, acc, g),
                (loss, load, dropped))

    g, (loss, load, dropped) = jax.lax.scan(
        body, jax.tree_util.tree_map(jnp.zeros_like, theta),
        (ids, seg, pos))
    return (jnp.sum(loss), g, n_targets,
            None if load is None else jnp.sum(load, axis=0),
            jnp.sum(dropped))


@functools.lru_cache(maxsize=16)
def _train_step_jit(spec: BlockSpec, lr: float, l2: float):
    """The one training program: a step's batch ``[n_micro, rows, L]``
    -> :func:`step_gradients` -> Adam on the float32 master parameters.
    The state (parameters, both moments, the step count: 16 bytes a
    parameter with the gradients) is donated, so it is updated in
    place."""
    import jax
    import jax.numpy as jnp

    def seq_train_step(state, ids, seg, pos, negs):
        theta, m, v, t = state
        loss, g, n_targets, load, dropped = step_gradients(
            theta, ids, seg, pos, negs, spec=spec, l2=l2)
        with jax.named_scope("adam"):
            t = t + 1
            m = jax.tree_util.tree_map(
                lambda mi, gi: ADAM_B1 * mi + (1 - ADAM_B1) * gi, m, g)
            v = jax.tree_util.tree_map(
                lambda vi, gi: ADAM_B2 * vi + (1 - ADAM_B2) * gi * gi,
                v, g)
            scale = lr * jnp.sqrt(1 - ADAM_B2 ** t) / (1 - ADAM_B1 ** t)
            theta = jax.tree_util.tree_map(
                lambda ti, mi, vi: ti - scale * mi
                / (jnp.sqrt(vi) + ADAM_EPS), theta, m, v)
        out = {"loss": loss, "targets": n_targets}
        if load is not None:
            per_expert = jnp.sum(load, axis=0).astype(jnp.float32)
            out.update(expert_max=jnp.max(per_expert),
                       expert_mean=jnp.mean(per_expert),
                       dropped=dropped)
        return (theta, m, v, t), out

    return jax.jit(seq_train_step, donate_argnums=0)


def plan_steps(buckets, params: SeqRecParams) -> List[Tuple[int, int]]:
    """Per-piece ``(steps, batch rows)`` the trainer will run:
    ``num_steps`` split proportionally to row counts (min 1 each),
    batch clipped to the piece. One definition shared by
    :func:`train_seqrec` and the bench's tokens/s accounting."""
    total_rows = sum(len(b) for b in buckets)
    if not total_rows:
        raise ValueError("plan_steps: no non-empty sequences to train "
                         "on (every user history was empty)")
    return [(max(1, round(int(params.num_steps)
                          * len(b) / total_rows)),
             min(int(params.batch_size), len(b)))
            for b in buckets]


def _step_batches(piece, steps: int, bs: int, micro_rows: int,
                  n_negs: int, n_items: int, rng):
    """The input pipeline: each step's rows drawn from the seed (with
    replacement, as a step samples its batch), cut into microbatches,
    and its shared negatives."""
    ids, seg, pos = piece.ids, piece.seg, piece.pos
    micro = micro_rows if 0 < micro_rows < bs and bs % micro_rows == 0 \
        else bs
    shape = (bs // micro, micro, piece.seq_len)
    for _ in range(steps):
        sel = rng.integers(0, len(piece), size=bs)
        negs = rng.integers(0, n_items, size=n_negs).astype(np.int32)
        yield (ids[sel].reshape(shape), seg[sel].reshape(shape),
               pos[sel].reshape(shape), negs)


def train_seqrec(buckets, n_items: int, params: SeqRecParams,
                 theta: Optional[Dict[str, Any]] = None,
                 to_host: bool = True
                 ) -> Tuple[Dict[str, Any], np.ndarray]:
    """Train the encoder over a layout: a list of padded buckets, or
    one :class:`PackedRows`.

    ``num_steps`` Adam steps in all, split across the layout's pieces
    proportionally to their row counts (every non-empty piece gets at
    least one). Each step's batch is drawn on the host from ``seed``
    and handed to ONE jitted step program per batch shape while the
    device still runs the step before (the state stays on the device
    and is donated from step to step; nothing is read back until the
    last step has been enqueued). Returns ``(theta, per-step losses)``;
    ``to_host=False`` leaves ``theta`` on the device for the encode
    that follows."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.utils import metrics as _metrics
    from predictionio_tpu.utils import tracing as _tracing

    pieces = [buckets] if isinstance(buckets, PackedRows) else list(buckets)
    if not pieces:
        raise ValueError("train_seqrec: no non-empty sequences to train "
                         "on (every user history was empty)")
    spec = block_spec(params)
    if spec.sdar is not None and int(params.num_steps) > 0:
        raise ValueError(
            "the sdar_moe block is not trained here: under its "
            "block-causal mask a position sees the rest of its block, so "
            "the next-item loss would read its own target; the "
            "masked-block diffusion objective is not implemented "
            "(ROADMAP Reach). Serve it with numSteps 0 and seededWeights")
    if spec.swa is not None and int(params.num_steps) > 0:
        raise ValueError(
            "the smallthinker block is not trained here (no training "
            "cell holds it: the packed rows of 4,096 never reach its "
            "window). Serve it with numSteps 0 and seededWeights")
    if spec.lin is not None and int(params.num_steps) > 0:
        raise ValueError(
            "the qwen3_next block is not trained here (the chunked "
            "rule has no backward pass and no training cell holds it: "
            "ROADMAP Reach). Serve it with numSteps 0 and seededWeights")
    if spec.hyb is not None and int(params.num_steps) > 0:
        raise ValueError(
            "the falcon_h1 block is not trained here (the chunked SSD "
            "form has no backward pass and no training cell holds it: "
            "ROADMAP Reach). Serve it with numSteps 0 and seededWeights")
    with _tracing.span("seq.stage"):
        if theta is None:
            theta = init_theta_device(n_items, params)
        else:   # the state is donated: never the caller's own arrays
            theta = {k: jnp.array(v, jnp.float32)
                     for k, v in theta.items()}
        state = (theta, jax.tree_util.tree_map(jnp.zeros_like, theta),
                 jax.tree_util.tree_map(jnp.zeros_like, theta),
                 jnp.zeros((), jnp.float32))
        jax.block_until_ready(state)
    run = _train_step_jit(spec, float(params.learning_rate),
                          float(params.l2))
    rng = np.random.default_rng([int(params.seed), 1])
    outs = []
    with _tracing.span("seq.steps"):
        compile_s0 = _metrics.JIT_COMPILE_SECONDS.value()
        t0 = _tracing.span_now()
        for piece, (steps, bs) in zip(pieces, plan_steps(pieces, params)):
            for batch in _step_batches(piece, steps, bs,
                                       int(params.micro_rows),
                                       int(params.n_negatives),
                                       int(n_items), rng):
                state, out = run(state, *batch)
                outs.append(out)
        compile_s = _metrics.JIT_COMPILE_SECONDS.value() - compile_s0
        if compile_s > 0:
            # a first call: trace, lower, compile (or cache load) ran
            # before the first step did
            _tracing.record_completed_span(
                "seq.compile", t0, min(t0 + compile_s, _tracing.span_now()))
        jax.block_until_ready(state)
    outs = jax.device_get(outs)
    losses = np.asarray([o["loss"] for o in outs], dtype=np.float32)
    _metrics.SEQ_TRAIN_TARGETS.inc(float(sum(o["targets"] for o in outs)))
    if "dropped" in outs[0]:
        dropped = int(sum(o["dropped"] for o in outs))
        _metrics.SEQ_DROPPED_TOKENS.inc(dropped)
        _metrics.SEQ_EXPERT_LOAD.set(
            float(np.mean([o["expert_max"] for o in outs])), stat="max")
        _metrics.SEQ_EXPERT_LOAD.set(
            float(np.mean([o["expert_mean"] for o in outs])), stat="mean")
        # dropless by construction: every (token, expert) pair was in
        # some expert's run
        assert dropped == 0, f"{dropped} (token, expert) pairs dropped"
    theta = state[0]
    if to_host:
        theta = {k: np.asarray(v, dtype=np.float32)
                 for k, v in theta.items()}
    return theta, losses


__all__ = [
    "OLMOE_1B_7B",
    "BlockSpec",
    "PackedRows",
    "SeqRecParams",
    "SequenceBucket",
    "block_spec",
    "length_bucket",
    "bucket_sequences",
    "pack_sequences",
    "init_theta",
    "init_theta_device",
    "encoder_forward",
    "encode_bucket",
    "encode_bucket_mesh",
    "encode_users",
    "output_table",
    "select_sp_kernel",
    "step_gradients",
    "plan_steps",
    "sampled_softmax_terms",
    "table_rows",
    "train_seqrec",
]
