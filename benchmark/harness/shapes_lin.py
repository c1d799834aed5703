"""Operations and bytes a dispatch of the long-session cell needs, from
shapes and counters alone (``shapes.py``'s rule: the mathematics, not
this implementation): what the least program would stream and multiply
for the same queries against the same slots and caches. Sizes come from
the configuration (``block`` below is ``models/linrec.py::block_of``);
counts from the lane's counters (``drivers/http_sess_long.py``:
``readers["lin"]``).

A dispatch is one forward of a group's new events (up to 8 queries x 8
token rows). It MUST read: every layer's mixer weights (a Gated
DeltaNet layer's two input projections, convolution and output
projection; an attention layer's q-and-gate, k, v and o), router,
shared expert and its gate; the experts a valid token row PICKED among
the 128 held (an expert picked by a padded row is not one); of every
query that brings events its session's SLOT in each DeltaNet layer,
read and written back (state and tail); in each attention layer the
key and value rows of the session's cached positions and the rows it
writes; the output table once.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping


def n_full(b: Mapping[str, Any]) -> int:
    return b["n_layers"] // b["interval"]


def n_gdn(b: Mapping[str, Any]) -> int:
    return b["n_layers"] - n_full(b)


def conv_width(b: Mapping[str, Any]) -> int:
    return 2 * b["k_heads"] * b["k_dim"] + b["v_heads"] * b["v_dim"]


def gdn_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One DeltaNet layer's mixer: in_proj_qkvz, in_proj_ba, out_proj
    (the compute dtype) and the convolution, A_log, dt_bias (float32)."""
    D, VW = b["hidden"], b["v_heads"] * b["v_dim"]
    return float((D * (conv_width(b) + VW) + D * 2 * b["v_heads"]
                  + VW * D) * weight_bytes
                 + (b["conv"] * conv_width(b) + 2 * b["v_heads"]) * 4)


def attn_weights(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One attention layer's mixer: q and gate, k, v, o."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    return float((2 * D * A + 2 * D * KW + A * D) * weight_bytes)


def layer_common(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """What every layer reads beside its mixer and its picked experts:
    the router and the shared expert's gate (float32), the shared
    expert."""
    D = b["hidden"]
    return float(D * b["n_experts"] * 4 + D * 4
                 + 3 * D * b["shared_width"] * weight_bytes)


def weights_fixed(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """Bytes every dispatch reads whatever its tokens."""
    return n_gdn(b) * gdn_weights(b, weight_bytes) \
        + n_full(b) * attn_weights(b, weight_bytes) \
        + b["n_layers"] * layer_common(b, weight_bytes) \
        + float(b["n_items"] * b["hidden"] * weight_bytes)


def weights_prefetched(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """The fixed weights the layers read (the mixers', the routers',
    the shared experts'): all of :func:`weights_fixed` but the head's
    table, which the head's own matmul reads."""
    return weights_fixed(b, weight_bytes) \
        - float(b["n_items"] * b["hidden"] * weight_bytes)


def expert_bytes(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One routed expert's three matrices."""
    return 3.0 * b["hidden"] * b["expert_width"] * weight_bytes


def slot_bytes(b: Mapping[str, Any], tail_bytes: int = 2) -> float:
    """ONE DeltaNet layer's slot: the float32 state and the tail."""
    return float(b["v_heads"] * b["k_dim"] * b["v_dim"] * 4
                 + (b["conv"] - 1) * conv_width(b) * tail_bytes)


def cache_row_bytes(b: Mapping[str, Any], cache_bytes: int = 2) -> float:
    """One cached position's key and value rows in ONE attention
    layer."""
    return 2.0 * b["n_kv"] * b["head_dim"] * cache_bytes


def state_bytes(w: Mapping[str, Any]) -> float:
    """Slot bytes the dispatches read and wrote back
    (``pio_sess_state_bytes_total``, both ways)."""
    return float(w["state_bytes_read"] + w["state_bytes_written"])


def dispatch_bytes(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Bytes one mean dispatch must stream."""
    n = max(float(w["dispatches"]), 1.0)
    written = w["tokens"] * n_full(b) * cache_row_bytes(b)
    return weights_fixed(b) + (
        w["experts_touched"] * expert_bytes(b) + state_bytes(w)
        + w["rows_read_full"] * cache_row_bytes(b) + written) / n


def cache_attention(w: Mapping[str, Any], b: Mapping[str, Any]
                    ) -> Dict[str, float]:
    """Attention over the paged caches: every cached row of a query's
    session read once an attention layer (key and value), scored and
    weighted by the query's new token rows (the window's mean a query)
    and every query head."""
    reads = float(w["rows_read_full"])
    rows = w["tokens"] / max(float(w["live_queries"]), 1.0)
    return {"bytes": reads * cache_row_bytes(b),
            "flops": 4.0 * reads * rows * b["n_heads"] * b["head_dim"]}


def gdn_step(w: Mapping[str, Any], b: Mapping[str, Any]) -> Dict[str, float]:
    """The DeltaNet layers of the dispatches: bytes: the mixer's
    weights a dispatch and layer, and each live query's slot in and
    out; operations: a token's projections (2 a multiply-add), its
    convolution, and the rule's four passes over a head's state (decay,
    S^T k, the rank-one update, S^T q: 2 operations an element
    each)."""
    D, VW = b["hidden"], b["v_heads"] * b["v_dim"]
    token = 2.0 * (D * (conv_width(b) + VW) + D * 2 * b["v_heads"]
                   + VW * D) + 2.0 * b["conv"] * conv_width(b) \
        + 8.0 * b["v_heads"] * b["k_dim"] * b["v_dim"]
    return {"bytes": w["dispatches"] * n_gdn(b) * gdn_weights(b)
            + state_bytes(w),
            "flops": w["tokens"] * n_gdn(b) * token}


def model_flops(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """The dispatches' model FLOPs: every new token through both kinds
    of mixer, the router, the experts it FOUND here (its picks among
    the 128 held) and the shared one in every layer, the head once a
    live query, and its attention over the cached rows."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    attn = 2.0 * (2 * D * A + 2 * D * KW + A * D)
    common = 2.0 * (D * b["n_experts"] + D + 3 * D * b["shared_width"])
    return gdn_step(w, b)["flops"] \
        + w["tokens"] * (n_full(b) * attn + b["n_layers"] * common) \
        + w["local_picks"] * 6.0 * D * b["expert_width"] \
        + w["live_queries"] * 2.0 * D * b["n_items"] \
        + cache_attention(w, b)["flops"]
