"""The sequentialrec template as the session cell serves it: a
``SeqRecModel`` of the ``glm_moe_dsa`` block with NO trained weights
(``theta`` empty: the deploy draws the seeded backbone on the device,
in one jitted call a tensor, from ``--seed``), the users' stored
histories, and the algorithm's parameters from the configuration's
PUBLISHED keys (the ``config.json`` names, as the file keeps them)."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data


def seqrec_params(config: Mapping[str, Any], seed: int):
    from predictionio_tpu.ops.seqrec import SeqRecParams

    c = config
    if c["model_type"] != "glm_moe_dsa" or c["hidden_act"] != "silu" \
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"] \
            or c["topk_method"] != "noaux_tc" or c["n_group"] != 1 \
            or c["attention_bias"] or c["tie_word_embeddings"] \
            or not (c["rope_interleave"] and c["indexer_rope_interleave"]) \
            or c["qk_head_dim"] != c["qk_nope_head_dim"] \
            + c["qk_rope_head_dim"] or c["num_nextn_predict_layers"]:
        raise ValueError("the session cell runs the glm_moe_dsa block as "
                         "published: silu experts, sigmoid noaux_tc "
                         "routing with renormalised weights, one group, "
                         "interleaved rotary pairs, no bias, untied tables, "
                         "no multi-token-prediction layer")
    return SeqRecParams(
        block="glm_moe_dsa", rank=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        n_dense_layers=int(c["first_k_dense_replace"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), positions="rope",
        rope_theta=float(c["rope_parameters"]["rope_theta"]), tied=False,
        vocab_rows=int(c["vocab_size"]),
        q_lora_rank=int(c["q_lora_rank"]),
        kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_head_dim=int(c["qk_nope_head_dim"]),
        qk_rope_head_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]),
        index_n_heads=int(c["index_n_heads"]),
        index_head_dim=int(c["index_head_dim"]),
        index_topk=int(c["index_topk"]),
        dense_width=int(c["intermediate_size"]),
        n_experts=int(c["router_outputs"]),
        expert_width=int(c["moe_intermediate_size"]),
        experts_per_token=int(c["num_experts_per_tok"]),
        n_shared_experts=int(c["n_shared_experts"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        experts_held=int(c["n_routed_experts"]),
        expert_share=int(c["expert_share"]),
        compute_dtype=str(c["compute_dtype"]),
        session_pool_tokens=int(c["session"]["pool_tokens"]),
        session_audit=int(c["check"]["audits"]),
        max_seq_len=int(c["session"]["max_tokens"]), num_steps=0,
        seeded_weights=True, seed=int(seed))


def output_table(config: Mapping[str, Any], seed: int):
    """The seed's output table alone (device, the served dtype), drawn
    by the same keys as the lane's whole backbone."""
    from predictionio_tpu.ops import mla

    params = seqrec_params(config, seed)
    V = int(config["vocab_size"])
    names = [n for n, _, _ in mla.theta_shapes(V, mla.glm_spec(params))]
    return mla.draw_serving_theta(
        V, params, skip=tuple(n for n in names if n != "out_emb"))["out_emb"]


def block_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes ``shapes_sess`` and ``oracle_glm5`` read."""
    c = config
    return {
        "n_layers": int(c["num_hidden_layers"]),
        "n_dense": int(c["first_k_dense_replace"]),
        "hidden": int(c["hidden_size"]), "width": int(c["hidden_size"]),
        "n_heads": int(c["num_attention_heads"]),
        "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
        "d_nope": int(c["qk_nope_head_dim"]),
        "d_rope": int(c["qk_rope_head_dim"]), "d_v": int(c["v_head_dim"]),
        "idx_heads": int(c["index_n_heads"]),
        "idx_dim": int(c["index_head_dim"]),
        "idx_topk": int(c["index_topk"]),
        "dense_width": int(c["intermediate_size"]),
        "expert_width": int(c["moe_intermediate_size"]),
        "n_experts": int(c["router_outputs"]),
        "per_token": int(c["num_experts_per_tok"]),
        "n_shared": int(c["n_shared_experts"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "held": int(c["n_routed_experts"]),
        "first": int(c["expert_share"]) * int(c["n_routed_experts"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_parameters"]["rope_theta"]),
        "n_items": int(c["shape"]["n_items"])}


def history_lengths(shape: Mapping[str, Any]) -> np.ndarray:
    """Log-uniform on [history_min, history_max], from ``data_seed``:
    the lengths fix every cache shape and the pool's fill, so a new
    ``--seed`` never changes them."""
    rng = np.random.default_rng(int(shape["data_seed"]))
    lo, hi = float(shape["history_min"]), float(shape["history_max"])
    return np.exp(rng.uniform(np.log(lo), np.log(hi),
                              int(shape["n_users"]))).astype(np.int64)


def histories(shape: Mapping[str, Any], seed: int) -> Dict[int, np.ndarray]:
    """Item ids by the popularity law over the slice, from ``--seed``."""
    rng = np.random.default_rng([int(seed), 11])
    cdf = np.cumsum(data.power_law_p(int(shape["n_items"]),
                                     data.ITEM_EXPONENT))
    out = {}
    for u, n in enumerate(history_lengths(shape).tolist()):
        ids = np.searchsorted(cdf, rng.random(n), side="right")
        out[u] = np.minimum(ids, int(shape["n_items"]) - 1).astype(np.int32)
    return out


def build(config: Mapping[str, Any], seed: int
          ) -> Tuple[list, Any, Dict[int, np.ndarray]]:
    """(models, engine params, the users' histories)."""
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.templates.sequentialrec.engine import (
        DataSourceParams,
        SeqRecModel,
    )

    shape = config["shape"]
    user_map, item_map = data.entity_maps(int(shape["n_users"]),
                                          int(shape["n_items"]))
    hist = histories(shape, seed)
    seen = {u: np.unique(h).astype(np.int64) for u, h in hist.items()}
    params = seqrec_params(config, seed)
    model = SeqRecModel(None, None, user_map, item_map, seen, {}, params,
                        int(config["session"]["max_tokens"]), hist)
    return [model], EngineParams(
        data_source_params=("", DataSourceParams(app_name="bench")),
        algorithm_params_list=[("seqrec", params)]), hist
