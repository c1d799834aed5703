"""The sequentialrec template as the hybrid-session cell serves it: a
``SeqRecModel`` of the ``falcon_h1`` block with NO trained weights
(``theta`` empty: the deploy draws the seeded backbone on the device
from ``--seed``), the users' stored histories, and the algorithm's
parameters from the configuration's PUBLISHED keys (Falcon-H1's
``config.json`` names, as the file keeps them). The histories' law is
``models/sessionrec.py``'s."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data
from benchmark.models import sessionrec


def seqrec_params(config: Mapping[str, Any], seed: int):
    from predictionio_tpu.ops.seqrec import SeqRecParams

    c = config
    if c["model_type"] != "falcon_h1" or c["hidden_act"] != "silu" \
            or c["tie_word_embeddings"] or c["rope_scaling"] is not None \
            or c["attention_bias"] or c["mlp_bias"] or c["mamba_proj_bias"] \
            or c["projectors_bias"] or not c["mamba_conv_bias"] \
            or not c["mamba_rms_norm"] or not c["mamba_use_mlp"] \
            or c["attn_layer_indices"] is not None \
            or c["mamba_norm_before_gate"] \
            or c["mamba_d_ssm"] != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("the hybrid-session cell runs the falcon_h1 block "
                         "as published: attention and Mamba-2 heads in "
                         "every layer, a silu SwiGLU behind them, a gated "
                         "RMS norm AFTER the gate in a mixer of heads x "
                         "head dim channels, a convolution with bias "
                         "and no other bias, untied tables, no rope scaling")
    return SeqRecParams(
        block="falcon_h1", rank=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        n_layers=int(c["num_hidden_layers"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), positions="rope",
        rope_theta=float(c["rope_theta"]), tied=False,
        vocab_rows=int(c["vocab_size"]),
        intermediate_size=int(c["intermediate_size"]),
        mamba_n_heads=int(c["mamba_n_heads"]),
        mamba_d_head=int(c["mamba_d_head"]),
        mamba_d_state=int(c["mamba_d_state"]),
        mamba_n_groups=int(c["mamba_n_groups"]),
        mamba_d_conv=int(c["mamba_d_conv"]),
        mamba_chunk_size=int(c["mamba_chunk_size"]),
        attention_in_multiplier=float(c["attention_in_multiplier"]),
        attention_out_multiplier=float(c["attention_out_multiplier"]),
        key_multiplier=float(c["key_multiplier"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        lm_head_multiplier=float(c["lm_head_multiplier"]),
        ssm_in_multiplier=float(c["ssm_in_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in c["ssm_multipliers"]),
        ssm_out_multiplier=float(c["ssm_out_multiplier"]),
        mlp_multipliers=tuple(float(m) for m in c["mlp_multipliers"]),
        compute_dtype=str(c["compute_dtype"]),
        session_pool_tokens=int(c["session"]["pool_tokens"]),
        session_audit=int(c["check"]["audits"]),
        max_seq_len=int(c["max_position_embeddings"]), num_steps=0,
        seeded_weights=True, seed=int(seed))


def output_table(config: Mapping[str, Any], seed: int):
    """The seed's output table alone (device, the served dtype), drawn
    by the same keys as the lane's whole backbone."""
    from predictionio_tpu.ops import falconh1

    params = seqrec_params(config, seed)
    V = int(config["vocab_size"])
    names = [n for n, _, _ in falconh1.theta_shapes(
        V, falconh1.hyb_spec(params))]
    return falconh1.draw_serving_theta(
        V, params, skip=tuple(n for n in names if n != "out_emb"))["out_emb"]


def block_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes ``shapes_hyb`` and ``oracle_falconh1`` read."""
    c = config
    return {
        "n_layers": int(c["num_hidden_layers"]),
        "hidden": int(c["hidden_size"]),
        "n_heads": int(c["num_attention_heads"]),
        "n_kv": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "ssm_heads": int(c["mamba_n_heads"]),
        "ssm_head_dim": int(c["mamba_d_head"]),
        "d_state": int(c["mamba_d_state"]),
        "n_groups": int(c["mamba_n_groups"]),
        "conv": int(c["mamba_d_conv"]),
        "mlp_width": int(c["intermediate_size"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
        "attn_in": float(c["attention_in_multiplier"]),
        "attn_out": float(c["attention_out_multiplier"]),
        "key_mult": float(c["key_multiplier"]),
        "emb_mult": float(c["embedding_multiplier"]),
        "head_mult": float(c["lm_head_multiplier"]),
        "ssm_in": float(c["ssm_in_multiplier"]),
        "ssm_mults": tuple(float(m) for m in c["ssm_multipliers"]),
        "ssm_out": float(c["ssm_out_multiplier"]),
        "mlp_mults": tuple(float(m) for m in c["mlp_multipliers"]),
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
