"""Operations and bytes a dispatch of the mixed-session cell needs,
from shapes and counters alone (``shapes.py``'s rule: the mathematics,
not this implementation): what the least program would stream and
multiply for the same queries against the same caches. Sizes come from
the configuration (``block`` below is ``models/swarec.py::block_of``);
counts from the lane's counters (``drivers/http_sess_mixed.py``:
``readers["swa"]``).

A dispatch is one forward of a group's new events (up to 8 queries x 8
token rows): it reads every layer's attention weights and router, the
experts a valid token row PICKED (not the 64 held), of every query's
session the key and value rows that are VISIBLE to its rows (a global
layer: all; a window layer: the newest 4,095 and its own), and the
output table once.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

# the bytes every dispatch reads whatever its tokens (q, k, v, o of
# every layer, the float32 routers, the output table) and one expert's
# three matrices: the slate cell's functions of the same sizes
from benchmark.harness.shapes_slate import (  # noqa: F401
    expert_bytes,
    weights_fixed,
)


def cache_row_bytes(b: Mapping[str, Any], cache_bytes: int = 2) -> float:
    """One cached position's key and value rows in ONE layer."""
    return 2.0 * b["n_kv"] * b["head_dim"] * cache_bytes


def rows_read(w: Mapping[str, Any]) -> float:
    """Cache rows the dispatches had to read, summed over queries and
    over the layers of both kinds (``pio_sess_cache_rows_read_total``)."""
    return float(w["rows_read_global"] + w["rows_read_window"])


def dispatch_bytes(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Bytes one mean dispatch must stream: the fixed weights, the
    experts picked, the cache rows visible, the rows it writes."""
    n = max(float(w["dispatches"]), 1.0)
    written = w["tokens"] * b["n_layers"] * cache_row_bytes(b)
    return weights_fixed(b) + (w["experts_touched"] * expert_bytes(b)
                               + rows_read(w) * cache_row_bytes(b)
                               + written) / n


def cache_attention(w: Mapping[str, Any], b: Mapping[str, Any]
                    ) -> Dict[str, float]:
    """Attention over the caches: every visible row read once a layer
    (key and value), scored and weighted by the query's new token rows
    (the window's mean a query) and every query head."""
    reads = rows_read(w)
    rows = w["tokens"] / max(float(w["queries"]), 1.0)
    return {"bytes": reads * cache_row_bytes(b),
            "flops": 4.0 * reads * rows * b["n_heads"] * b["head_dim"]}


def model_flops(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """The dispatches' model FLOPs: every new token through the
    projections, the router and its picked experts in every layer, the
    head once a query, and its attention over the visible rows."""
    D, A, KW = b["hidden"], b["n_heads"] * b["head_dim"], \
        b["n_kv"] * b["head_dim"]
    layer = 2.0 * (2 * D * A + 2 * D * KW + D * b["n_experts"]
                   + 3 * D * b["expert_width"] * b["per_token"])
    return w["tokens"] * b["n_layers"] * layer \
        + w["queries"] * 2.0 * D * b["n_items"] \
        + cache_attention(w, b)["flops"]


def one_table_rows(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Row-layers ONE block table for every layer would hold: what the
    global kind holds a layer, in every layer."""
    return float(w["kind_tokens_global"]) * b["n_layers"]


def held_rows(w: Mapping[str, Any], b: Mapping[str, Any]) -> float:
    """Row-layers the two tables hold."""
    n_window = sum(b["pattern"])
    return float(w["kind_tokens_global"]) * (b["n_layers"] - n_window) \
        + float(w["kind_tokens_window"]) * n_window
