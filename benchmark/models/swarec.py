"""The sequentialrec template as the mixed-session cell serves it: a
``SeqRecModel`` of the ``smallthinker`` block with NO trained weights
(``theta`` empty: the deploy draws the seeded backbone on the device
from ``--seed``), the users' stored histories, and the algorithm's
parameters from the configuration's PUBLISHED keys (SmallThinker's
``config.json`` names, as the file keeps them). The histories' law is
``models/sessionrec.py``'s."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data
from benchmark.models import sessionrec


def seqrec_params(config: Mapping[str, Any], seed: int):
    from predictionio_tpu.ops.seqrec import SeqRecParams

    c = config
    if c["model_name"] != "smallthinker_21b_instruct" \
            or not c["moe_primary_router_apply_softmax"] \
            or not c["norm_topk_prob"] or c["tie_word_embeddings"] \
            or c["rope_scaling"] is not None \
            or list(c["rope_layout"]) != list(c["sliding_window_layout"]):
        raise ValueError("the mixed-session cell runs the smallthinker "
                         "block as published: a softmax over the picked "
                         "logits, untied tables, no rope scaling, the "
                         "window layers the rotated ones")
    return SeqRecParams(
        block="smallthinker", rank=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        n_layers=int(c["num_hidden_layers"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), positions="rope",
        rope_theta=float(c["rope_theta"]), tied=False,
        vocab_rows=int(c["vocab_size"]),
        n_experts=int(c["moe_num_primary_experts"]),
        expert_width=int(c["moe_ffn_hidden_size"]),
        experts_per_token=int(c["moe_num_active_primary_experts"]),
        norm_topk_prob=True,
        sliding_window_size=int(c["sliding_window_size"]),
        sliding_window_layout=tuple(
            int(g) for g in c["sliding_window_layout"]),
        compute_dtype=str(c["compute_dtype"]),
        session_pool_tokens=int(c["session"]["pool_tokens"]),
        session_audit=int(c["check"]["audits"]),
        max_seq_len=int(c["max_position_embeddings"]), num_steps=0,
        seeded_weights=True, seed=int(seed))


def output_table(config: Mapping[str, Any], seed: int):
    """The seed's output table alone (device, the served dtype), drawn
    by the same keys as the lane's whole backbone."""
    from predictionio_tpu.ops import smallthinker

    params = seqrec_params(config, seed)
    V = int(config["vocab_size"])
    names = [n for n, _, _ in smallthinker.theta_shapes(
        V, smallthinker.swa_spec(params))]
    return smallthinker.draw_serving_theta(
        V, params, skip=tuple(n for n in names if n != "out_emb"))["out_emb"]


def block_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes ``shapes_swa`` and ``oracle_smallthinker`` read."""
    c = config
    L = int(c["num_hidden_layers"])
    return {
        "n_layers": L, "hidden": int(c["hidden_size"]),
        "n_heads": int(c["num_attention_heads"]),
        "n_kv": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "window": int(c["sliding_window_size"]),
        "pattern": tuple(int(g) for g in c["sliding_window_layout"])[:L],
        "expert_width": int(c["moe_ffn_hidden_size"]),
        "n_experts": int(c["moe_num_primary_experts"]),
        "per_token": int(c["moe_num_active_primary_experts"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
        "n_items": int(c["shape"]["n_items"])}


def probe_user(config: Mapping[str, Any]) -> int:
    """The check's own session's user: the one behind the traffic's."""
    return int(config["shape"]["n_users"])


def build(config: Mapping[str, Any], seed: int
          ) -> Tuple[list, Any, Dict[int, np.ndarray]]:
    """(models, engine params, the users' histories): the shape's
    users, whom the traffic asks, and one more with a history of
    ``check.probe_session`` events, whom only the check's probes ask."""
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.templates.sequentialrec.engine import (
        DataSourceParams,
        SeqRecModel,
    )

    shape = config["shape"]
    n_items = int(shape["n_items"])
    if n_items != int(config["vocab_size"]):
        raise ValueError("the catalog is the vocabulary, whole")
    user_map, item_map = data.entity_maps(int(shape["n_users"]) + 1,
                                          n_items)
    hist = sessionrec.histories(shape, seed)
    hist[probe_user(config)] = np.random.default_rng(
        [int(seed), 12]).integers(
            0, n_items, int(config["check"]["probe_session"])).astype(
                np.int32)
    seen = {u: np.unique(h).astype(np.int64) for u, h in hist.items()}
    params = seqrec_params(config, seed)
    model = SeqRecModel(None, None, user_map, item_map, seen, {}, params,
                        int(config["max_position_embeddings"]), hist)
    return [model], EngineParams(
        data_source_params=("", DataSourceParams(app_name="bench")),
        algorithm_params_list=[("seqrec", params)]), hist
