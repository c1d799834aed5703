"""Operations and bytes the sequence backbone's kernels need, from
shapes alone (``shapes.py``'s rule: the mathematics, not this
implementation). FORWARD counts; a training step's backward pass is
twice the forward's matmuls, so ``passes`` is 3 for a step and 1 for
an encode.

What the least program must do: every token goes through
``experts_per_token`` experts, never all of them; a position attends
the positions before it IN ITS OWN SEGMENT, never the whole row, so a
packed row of short histories needs far fewer attention operations
than a causal 4,096 x 4,096 triangle. A kernel that computes the whole
triangle and masks shows that as a low roofline share, which is the
point of the number.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Sequence

import numpy as np


def moe_gmm(tokens: float, hidden: int, expert_width: int, n_experts: int,
            per_token: int, passes: int = 1,
            weight_bytes: int = 2, act_bytes: int = 2) -> Dict[str, float]:
    """The three grouped matmuls of the expert layer for ``tokens``
    tokens: gate and up ``[hidden -> width]``, down ``[width ->
    hidden]``, each token through ``per_token`` experts.

    flops: ``2 * tokens * per_token * 3 * hidden * width`` a pass.
    bytes a pass: every expert's three matrices read once; the
    dispatched rows read by gate and up (once: they share them) and
    the activations between the matmuls written and read once each;
    the down product written."""
    pairs = float(tokens) * per_token
    flops = passes * 2.0 * pairs * 3 * hidden * expert_width
    weights = 3.0 * n_experts * hidden * expert_width * weight_bytes
    acts = pairs * act_bytes * (hidden + 2 * 2 * expert_width + hidden)
    return {"flops": flops, "bytes": passes * (weights + acts)}


def segment_pairs(segment_lengths: Sequence[int]) -> float:
    """(query, key) pairs of causal attention inside segments: ``n *
    (n + 1) / 2`` for a segment of ``n`` tokens."""
    n = np.asarray(segment_lengths, dtype=np.float64)
    return float(np.sum(n * (n + 1) / 2))


def attention(pairs: float, tokens: float, n_heads: int, head_dim: int,
              passes: int = 1, act_bytes: int = 2) -> Dict[str, float]:
    """Softmax attention over ``pairs`` (query, key) pairs a head:
    ``q . k`` and ``p . v`` are ``2 * head_dim`` flops each a pair and
    head. bytes: q, k, v read and the output written once a pass."""
    flops = passes * 2.0 * 2 * pairs * n_heads * head_dim
    bytes_ = passes * 4.0 * tokens * n_heads * head_dim * act_bytes
    return {"flops": flops, "bytes": bytes_}


def model_flops(tokens: float, pairs: float, targets: float,
                block: Mapping[str, Any], n_negatives: int,
                passes: int) -> float:
    """Model FLOPs of ``tokens`` tokens through the backbone: the four
    attention projections, segment-causal attention, the router, the
    experts, and (a step only) the sampled softmax's logits on
    ``targets`` positions. Norms, rotary positions, softmax and the
    optimizer are not matmuls and are not counted; recomputed
    operations would not be either."""
    D, H, Dh = block["hidden"], block["n_heads"], block["head_dim"]
    per_layer = (2.0 * tokens * 4 * D * H * Dh
                 + 2.0 * tokens * D * block["n_experts"]
                 + moe_gmm(tokens, D, block["expert_width"],
                           block["n_experts"], block["per_token"])["flops"]
                 + attention(pairs, tokens, H, Dh)["flops"])
    total = block["n_layers"] * per_layer
    if passes == 3:
        total += 2.0 * targets * (1 + n_negatives) * D
    return passes * total
