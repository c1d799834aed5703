"""Operations and bytes a dispatch of the hybrid-session cell needs,
from shapes and counters alone (``shapes.py``'s rule: the mathematics,
not this implementation): what the least program would stream and
multiply for the same queries against the same slots and caches. Sizes
come from the configuration (``block`` below is
``models/hybrec.py::block_of``); counts from the lane's counters
(``drivers/http_sess_hybrid.py``: ``readers["hyb"]``).

A dispatch is one forward of a group's new events (up to 8 queries x 8
token rows) through a DENSE model: it MUST read every layer's weights
whole (the attention heads' q, k, v and o; the Mamba-2 mixer's two
projections, convolution with bias, gated norm, A_log, D, dt_bias; the
SwiGLU's three matrices; the norms); of every query that brings events
its session's SLOT in each layer, read and written back (state and
tail); in each layer the key and value rows of the session's cached
positions and the rows it writes; the output table once.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping


def d_ssm(b: Mapping[str, Any]) -> int:
    return b["ssm_heads"] * b["ssm_head_dim"]


def conv_width(b: Mapping[str, Any]) -> int:
    return d_ssm(b) + 2 * b["n_groups"] * b["d_state"]


def in_width(b: Mapping[str, Any]) -> int:
    return d_ssm(b) + conv_width(b) + b["ssm_heads"]


def attn_params(b: Mapping[str, Any]) -> int:
    """One layer's attention heads: q, k, v, o."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    return 2 * D * A + 2 * D * KW


def ssm_matmul_params(b: Mapping[str, Any]) -> int:
    """One layer's Mamba-2 mixer's two projections."""
    return b["hidden"] * in_width(b) + d_ssm(b) * b["hidden"]


def attn_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    return float(attn_params(b) * weight_bytes)


def ssm_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One layer's mixer: in_proj and out_proj (the compute dtype), the
    convolution and its bias, the gated norm's weight, A_log, D and
    dt_bias (float32)."""
    return float(ssm_matmul_params(b) * weight_bytes
                 + ((b["conv"] + 1) * conv_width(b) + d_ssm(b)
                    + 3 * b["ssm_heads"]) * 4)


def mlp_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    return float(3 * b["hidden"] * b["mlp_width"] * weight_bytes)


def layer_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """Everything one layer reads whatever its tokens (the two norms'
    weights are float32)."""
    return attn_weights(b, weight_bytes) + ssm_weights(b, weight_bytes) \
        + mlp_weights(b, weight_bytes) + 2.0 * b["hidden"] * 4


def weights_prefetched(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """The weights the layers read: all of :func:`weights_fixed` but
    the head's table, which the head's own matmul reads."""
    return b["n_layers"] * layer_weights(b, weight_bytes)


def weights_fixed(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """Bytes every dispatch reads whatever its tokens: the model is
    dense."""
    return weights_prefetched(b, weight_bytes) \
        + float(b["n_items"] * b["hidden"] * weight_bytes)


def slot_bytes(b: Mapping[str, Any], tail_bytes: int = 2) -> float:
    """ONE layer's slot: the float32 state and the tail."""
    return float(b["ssm_heads"] * b["ssm_head_dim"] * b["d_state"] * 4
                 + (b["conv"] - 1) * conv_width(b) * tail_bytes)


def cache_row_bytes(b: Mapping[str, Any], cache_bytes: int = 2) -> float:
    """One cached position's key and value rows in ONE layer."""
    return 2.0 * b["n_kv"] * b["head_dim"] * cache_bytes


def state_bytes(w: Mapping[str, Any]) -> float:
    """Slot bytes the dispatches read and wrote back
    (``pio_sess_state_bytes_total``, both ways)."""
    return float(w["state_bytes_read"] + w["state_bytes_written"])


def dispatch_bytes(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Bytes one mean dispatch must stream (``rows_read_attn`` is
    summed over the layers already)."""
    n = max(float(w["dispatches"]), 1.0)
    written = w["tokens"] * b["n_layers"] * cache_row_bytes(b)
    return weights_fixed(b) + (
        state_bytes(w) + w["rows_read_attn"] * cache_row_bytes(b)
        + written) / n


def cache_attention(w: Mapping[str, Any], b: Mapping[str, Any]
                    ) -> Dict[str, float]:
    """Attention over the paged caches: every cached row of a query's
    session read once a layer (key and value), scored and weighted by
    the query's new token rows (the window's mean a query) and every
    query head."""
    reads = float(w["rows_read_attn"])
    rows = w["tokens"] / max(float(w["live_queries"]), 1.0)
    return {"bytes": reads * cache_row_bytes(b),
            "flops": 4.0 * reads * rows * b["n_heads"] * b["head_dim"]}


def ssd_step(w: Mapping[str, Any], b: Mapping[str, Any]) -> Dict[str, float]:
    """The Mamba-2 mixers of the dispatches: bytes: the mixer's weights
    a dispatch and layer, and each live query's slot in and out;
    operations: a token's two projections (2 a multiply-add), its
    convolution, and the scan's three passes over a head's state (the
    decay, the outer product's multiply-add, the read ``S C``: 6
    operations an element)."""
    token = 2.0 * ssm_matmul_params(b) + 2.0 * b["conv"] * conv_width(b) \
        + 6.0 * b["ssm_heads"] * b["ssm_head_dim"] * b["d_state"]
    return {"bytes": w["dispatches"] * b["n_layers"] * ssm_weights(b)
            + state_bytes(w),
            "flops": w["tokens"] * b["n_layers"] * token}


def model_flops(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """The dispatches' model FLOPs: every new token through the
    attention heads' projections, the Mamba-2 mixer and the SwiGLU of
    every layer, the head once a live query, and its attention over the
    cached rows."""
    token = 2.0 * attn_params(b) + 6.0 * b["hidden"] * b["mlp_width"]
    return ssd_step(w, b)["flops"] + w["tokens"] * b["n_layers"] * token \
        + w["live_queries"] * 2.0 * b["hidden"] * b["n_items"] \
        + cache_attention(w, b)["flops"]
