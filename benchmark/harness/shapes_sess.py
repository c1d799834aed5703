"""Operations and bytes a dispatch of the session lane needs, from
shapes alone (``shapes.py``'s rule: the mathematics, not this
implementation): what the least program would stream and multiply for
the same queries against the same caches. Sizes come from the
configuration (``block`` below is ``models/sessionrec.py::block_of``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping


def weights_fixed(b: Mapping[str, Any], weight_bytes: int = 2
                  ) -> Dict[str, float]:
    """Parameters every dispatch reads whatever its tokens: attention
    and indexer of every layer, the dense layers' feed-forward, the
    shared expert and router of every expert layer, the output slice."""
    D, H = b["hidden"], b["n_heads"]
    attn = (D * b["q_rank"] + b["q_rank"] * H * (b["d_nope"] + b["d_rope"])
            + D * (b["kv_rank"] + b["d_rope"])
            + b["kv_rank"] * H * (b["d_nope"] + b["d_v"]) + H * b["d_v"] * D)
    index = b["q_rank"] * b["idx_heads"] * b["idx_dim"] + D * b["idx_dim"] \
        + D * b["idx_heads"]
    dense = 3 * D * b["dense_width"]
    shared = 3 * D * b["expert_width"] * b["n_shared"]
    router = D * b["n_experts"]
    n_moe = b["n_layers"] - b["n_dense"]
    params = (b["n_layers"] * (attn + index) + b["n_dense"] * dense
              + n_moe * (shared + router) + b["n_items"] * D)
    return {"params": float(params), "bytes": float(params) * weight_bytes}


def expert_bytes(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One routed expert's three matrices."""
    return 3.0 * b["hidden"] * b["expert_width"] * weight_bytes


def index_key_bytes(positions: float, b: Mapping[str, Any],
                    cache_bytes: int = 2) -> float:
    """The index keys read for ``positions`` cached positions (summed
    over queries: a query's keys are read once for all its new tokens),
    every layer."""
    return positions * b["n_layers"] * b["idx_dim"] * cache_bytes


def index_flops(eligible: float, b: Mapping[str, Any]) -> float:
    """``eligible``: (token, key) pairs scored, summed over layers."""
    return 2.0 * eligible * b["idx_heads"] * b["idx_dim"]


def sparse_attention(selected: float, b: Mapping[str, Any],
                     cache_bytes: int = 2) -> Dict[str, float]:
    """Attention over the selected latents. ``selected``: (token, key)
    pairs kept, summed over layers. Each pair reads one latent row
    (``kv_rank + d_rope`` values) and costs, a head, the score product
    over the row and the weighted sum over ``kv_rank``."""
    row = b["kv_rank"] + b["d_rope"]
    return {"bytes": selected * row * cache_bytes,
            "flops": 2.0 * selected * b["n_heads"] * (row + b["kv_rank"])}


def dispatch_bytes(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Bytes one mean dispatch of the window must stream: the weights
    every dispatch reads, the routed experts that got a row, the index
    keys its queries see, the latent rows its tokens selected, and the
    cache rows it writes."""
    n = max(float(w["dispatches"]), 1.0)
    written = w["tokens"] * b["n_layers"] * (
        b["kv_rank"] + b["d_rope"] + b["idx_dim"]) * 2.0
    return (weights_fixed(b)["bytes"]
            + (w["experts_touched"] * expert_bytes(b)
               + index_key_bytes(w["positions"], b)
               + sparse_attention(w["selected"], b)["bytes"]
               + written) / n)
