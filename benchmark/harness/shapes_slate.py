"""Operations and bytes the slate lane's rounds need, from shapes alone
(``shapes.py``'s rule: the mathematics, not this implementation): what
the least program would stream and multiply for the same passes of the
same queries against the same caches. Sizes come from the configuration
(``block`` below is ``models/slaterec.py::block_of``); counts from the
lane's counters (``drivers/http_slates.py``: ``readers["slate"]``).

A device PASS is one forward of a group's blocks (up to 8 query rows x
4 token rows): it reads every layer's attention weights and router,
the experts a valid token row PICKED (not the 128 held), the key and
value rows of the group's sessions and scratch, and the output table.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping


def weights_fixed(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """Bytes every pass reads whatever its tokens: q, k, v, o of every
    layer, the routers (float32), the output table."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    attn = 2 * D * A + 2 * D * KW
    return float(b["n_layers"] * (attn * weight_bytes
                                  + D * b["n_experts"] * 4)
                 + b["n_items"] * D * weight_bytes)


def expert_bytes(b: Mapping[str, Any], weight_bytes: int = 2) -> float:
    """One expert's three matrices."""
    return 3.0 * b["hidden"] * b["expert_width"] * weight_bytes


def cache_row_bytes(b: Mapping[str, Any], cache_bytes: int = 2) -> float:
    """One cached position's key and value rows, every layer."""
    return 2.0 * b["n_layers"] * b["n_kv"] * b["head_dim"] * cache_bytes


def pass_bytes(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Bytes one mean device pass must stream."""
    n = max(float(w["passes_device"]), 1.0)
    return weights_fixed(b) + (w["experts_touched"] * expert_bytes(b)
                               + w["cache_rows_read"] * cache_row_bytes(b)
                               ) / n


def rows_a_block(w: Mapping[str, Any]) -> float:
    """Generated token rows of a mean block (its tail rows are left
    out: the count errs low, never high)."""
    return w["unmasked"] / max(float(w["rounds"]), 1.0)


def cache_attention(w: Mapping[str, Any], b: Mapping[str, Any]
                    ) -> Dict[str, float]:
    """Attention over the cached rows: every cached row read once a
    pass and layer (key and value), scored and weighted by each of the
    block's rows and every query head."""
    reads = float(w["cache_rows_read"])
    return {"bytes": reads * cache_row_bytes(b),
            "flops": 4.0 * reads * rows_a_block(w) * b["n_layers"]
            * b["n_heads"] * b["head_dim"]}


def model_flops(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """The rounds' model FLOPs: every generated token row of every
    query row's own passes through projections, router, its 8 experts
    and the head, and its attention over the cache."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    layer = 2.0 * (2 * D * A + 2 * D * KW + D * b["n_experts"]
                   + 3 * D * b["expert_width"] * b["per_token"])
    token = b["n_layers"] * layer + 2.0 * D * b["n_items"]
    return w["passes_query"] * rows_a_block(w) * token \
        + cache_attention(w, b)["flops"]
