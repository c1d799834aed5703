"""The sequentialrec template as the slate cell serves it: a
``SeqRecModel`` of the ``sdar_moe`` block with NO trained weights
(``theta`` empty: the deploy draws the seeded backbone on the device
from ``--seed``), the users' stored histories, and the algorithm's
parameters from the configuration's PUBLISHED keys (the ``config.json``
names, as the file keeps them) and its ``generation`` settings."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data
from benchmark.models import sessionrec


def seqrec_params(config: Mapping[str, Any], seed: int):
    from predictionio_tpu.ops.seqrec import SeqRecParams

    c, g = config, config["generation"]
    if c["model_type"] != "sdar_moe" or c["hidden_act"] != "silu" \
            or not c["norm_topk_prob"] or c["attention_bias"] \
            or c["tie_word_embeddings"] or c["mlp_only_layers"] \
            or c["decoder_sparse_step"] != 1 or c["use_sliding_window"] \
            or c["rope_scaling"] is not None:
        raise ValueError("the slate cell runs the sdar_moe block as "
                         "published: silu experts in every layer, softmax "
                         "routing with renormalised weights, no bias, "
                         "untied tables, full attention, plain rotary "
                         "positions")
    return SeqRecParams(
        block="sdar_moe", rank=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), n_layers=int(c["num_hidden_layers"]),
        norm="rmsnorm", norm_eps=float(c["rms_norm_eps"]),
        positions="rope", rope_theta=float(c["rope_theta"]), tied=False,
        vocab_rows=int(c["vocab_size"]), n_experts=int(c["num_experts"]),
        expert_width=int(c["moe_intermediate_size"]),
        experts_per_token=int(c["num_experts_per_tok"]),
        norm_topk_prob=True, block_length=int(g["block_length"]),
        denoising_steps=int(g["denoising_steps"]),
        remasking=str(g["remasking"]),
        confidence_threshold=float(g["confidence_threshold"]),
        mask_token=int(g["mask_token_id"]),
        compute_dtype=str(c["compute_dtype"]),
        session_pool_tokens=int(c["session"]["pool_tokens"]),
        session_audit=int(c["check"]["audits"]),
        max_seq_len=int(c["session"]["max_tokens"]), num_steps=0,
        seeded_weights=True, seed=int(seed))


def output_table(config: Mapping[str, Any], seed: int):
    """The seed's output table alone (device, the served dtype), drawn
    by the same keys as the lane's whole backbone."""
    from predictionio_tpu.ops import sdar

    params = seqrec_params(config, seed)
    V = int(config["vocab_size"])
    names = [n for n, _, _ in sdar.theta_shapes(V, sdar.sdar_spec(params))]
    return sdar.draw_serving_theta(
        V, params, skip=tuple(n for n in names if n != "out_emb"))["out_emb"]


def block_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes ``shapes_slate`` and ``oracle_sdar`` read."""
    c, g = config, config["generation"]
    return {
        "n_layers": int(c["num_hidden_layers"]),
        "hidden": int(c["hidden_size"]),
        "n_heads": int(c["num_attention_heads"]),
        "n_kv": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "expert_width": int(c["moe_intermediate_size"]),
        "n_experts": int(c["num_experts"]),
        "per_token": int(c["num_experts_per_tok"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
        "block_len": int(g["block_length"]), "steps": int(g["denoising_steps"]),
        "remasking": str(g["remasking"]),
        "threshold": float(g["confidence_threshold"]),
        "mask_id": int(g["mask_token_id"]),
        # rows the lane serves (the mask token's among them, never
        # generated), and the item ids the traffic draws from
        "n_items": int(c["vocab_size"]),
        "n_ids": int(c["shape"]["n_items"])}


def skip_mask(ids: np.ndarray, mask_id: int) -> np.ndarray:
    """Ranks over the catalog's ``rows - 1`` items -> table rows: the
    mask token's row is no item."""
    ids = np.asarray(ids)
    return (ids + (ids >= mask_id)).astype(np.int32)


def histories(config: Mapping[str, Any], seed: int) -> Dict[int, np.ndarray]:
    """``sessionrec.histories`` (the lengths from ``data_seed``, the
    items by the popularity law from ``--seed``) over the catalog less
    the mask token's row."""
    mask_id = int(config["generation"]["mask_token_id"])
    return {u: skip_mask(h, mask_id)
            for u, h in sessionrec.histories(config["shape"], seed).items()}


def short_user(config: Mapping[str, Any]) -> int:
    """The short check session's user: the one behind the traffic's."""
    return int(config["shape"]["n_users"])


def build(config: Mapping[str, Any], seed: int
          ) -> Tuple[list, Any, Dict[int, np.ndarray]]:
    """(models, engine params, the users' histories): the shape's
    users, whom the traffic asks, and one more with a history of
    ``check.short_session`` uniform items, whom only the check's probes
    ask (``harness/slate_check.py`` says why)."""
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.templates.sequentialrec.engine import (
        DataSourceParams,
        SeqRecModel,
    )

    from predictionio_tpu.data.bimap import StringIndexBiMap

    shape = config["shape"]
    rows, mask_id = int(config["vocab_size"]), int(
        config["generation"]["mask_token_id"])
    if int(shape["n_items"]) != rows - 1:
        raise ValueError("the catalog is the vocabulary less the mask "
                         "token's row")
    user_map, _ = data.entity_maps(int(shape["n_users"]) + 1, 1)
    # item id i<j> lives in row j, or j + 1 behind the mask token's row
    labels = np.char.add("i", np.arange(rows - 1).astype(str)).astype(object)
    item_map = StringIndexBiMap.from_distinct(
        np.insert(labels, mask_id, "<mask>"))
    hist = histories(config, seed)
    hist[short_user(config)] = skip_mask(
        np.random.default_rng([int(seed), 12]).integers(
            0, int(shape["n_items"]), int(config["check"]["short_session"])),
        mask_id)
    seen = {u: np.unique(h).astype(np.int64) for u, h in hist.items()}
    params = seqrec_params(config, seed)
    model = SeqRecModel(None, None, user_map, item_map, seen, {}, params,
                        int(config["session"]["max_tokens"]), hist)
    return [model], EngineParams(
        data_source_params=("", DataSourceParams(app_name="bench")),
        algorithm_params_list=[("seqrec", params)]), hist
