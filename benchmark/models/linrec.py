"""The sequentialrec template as the long-session cell serves it: a
``SeqRecModel`` of the ``qwen3_next`` block with NO trained weights
(``theta`` empty: the deploy draws the seeded backbone on the device
from ``--seed``), the users' stored histories, and the algorithm's
parameters from the configuration's PUBLISHED keys (Qwen3-Next's
``config.json`` names, as the file keeps them; ``num_experts`` is the
experts HELD here of the router's ``router_outputs``). The histories'
law is ``models/sessionrec.py``'s."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data
from benchmark.models import sessionrec


def seqrec_params(config: Mapping[str, Any], seed: int):
    from predictionio_tpu.ops.seqrec import SeqRecParams

    c = config
    if c["model_type"] != "qwen3_next" or c["hidden_act"] != "silu" \
            or not c["norm_topk_prob"] or c["tie_word_embeddings"] \
            or c["rope_scaling"] is not None or c["use_sliding_window"] \
            or c["decoder_sparse_step"] != 1 or c["mlp_only_layers"]:
        raise ValueError("the long-session cell runs the qwen3_next block "
                         "as published: silu experts in every layer, "
                         "renormalised softmax routing, untied tables, no "
                         "rope scaling, no sliding window")
    return SeqRecParams(
        block="qwen3_next", rank=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        n_layers=int(c["num_hidden_layers"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), positions="rope",
        rope_theta=float(c["rope_theta"]),
        partial_rotary_factor=float(c["partial_rotary_factor"]), tied=False,
        vocab_rows=int(c["vocab_size"]),
        n_experts=int(c["router_outputs"]),
        experts_held=int(c["num_experts"]),
        expert_share=int(c["expert_share"]),
        expert_width=int(c["moe_intermediate_size"]),
        experts_per_token=int(c["num_experts_per_tok"]),
        norm_topk_prob=True,
        shared_expert_width=int(c["shared_expert_intermediate_size"]),
        linear_key_heads=int(c["linear_num_key_heads"]),
        linear_value_heads=int(c["linear_num_value_heads"]),
        linear_key_head_dim=int(c["linear_key_head_dim"]),
        linear_value_head_dim=int(c["linear_value_head_dim"]),
        linear_conv_kernel=int(c["linear_conv_kernel_dim"]),
        full_attention_interval=int(c["full_attention_interval"]),
        compute_dtype=str(c["compute_dtype"]),
        session_pool_tokens=int(c["session"]["pool_tokens"]),
        session_audit=int(c["check"]["audits"]),
        max_seq_len=int(c["max_position_embeddings"]), num_steps=0,
        seeded_weights=True, seed=int(seed))


def output_table(config: Mapping[str, Any], seed: int):
    """The seed's output table alone (device, the served dtype), drawn
    by the same keys as the lane's whole backbone."""
    from predictionio_tpu.ops import qwen3next

    params = seqrec_params(config, seed)
    V = int(config["vocab_size"])
    names = [n for n, _, _ in qwen3next.theta_shapes(
        V, qwen3next.lin_spec(params))]
    return qwen3next.draw_serving_theta(
        V, params, skip=tuple(n for n in names if n != "out_emb"))["out_emb"]


def block_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes ``shapes_lin`` and ``oracle_qwen3next`` read."""
    c = config
    d = int(c["head_dim"])
    return {
        "n_layers": int(c["num_hidden_layers"]),
        "interval": int(c["full_attention_interval"]),
        "hidden": int(c["hidden_size"]),
        "n_heads": int(c["num_attention_heads"]),
        "n_kv": int(c["num_key_value_heads"]), "head_dim": d,
        "rot_dim": int(round(d * float(c["partial_rotary_factor"]))),
        "k_heads": int(c["linear_num_key_heads"]),
        "v_heads": int(c["linear_num_value_heads"]),
        "k_dim": int(c["linear_key_head_dim"]),
        "v_dim": int(c["linear_value_head_dim"]),
        "conv": int(c["linear_conv_kernel_dim"]),
        "expert_width": int(c["moe_intermediate_size"]),
        "shared_width": int(c["shared_expert_intermediate_size"]),
        "n_experts": int(c["router_outputs"]),
        "held": int(c["num_experts"]),
        "first": int(c["expert_share"]) * int(c["num_experts"]),
        "per_token": int(c["num_experts_per_tok"]),
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
        raise ValueError("the catalog is this chip's slice of the "
                         "vocabulary, whole")
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
