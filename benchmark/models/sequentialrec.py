"""The sequentialrec template as the sequence cell runs it: the
algorithm's and the preparator's parameters from the configuration's
PUBLISHED keys (the ``config.json`` names, as the configuration file
keeps them), and the users' event columns for the template's
preparator."""

from typing import Any, Dict, Mapping, Tuple

import numpy as np

from benchmark.harness import data


def seqrec_params(config: Mapping[str, Any], seed: int):
    """``SeqRecParams`` of the configuration's block and job."""
    from predictionio_tpu.ops.seqrec import SeqRecParams

    if config["model_type"] != "olmoe" or config["hidden_act"] != "silu" \
            or config["norm_topk_prob"] or config["attention_bias"] \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the sequence cell runs the olmoe block as "
                         "published: silu experts, no bias, no grouped "
                         "keys, router weights not renormalised")
    heads = int(config["num_attention_heads"])
    tr = config["train"]
    return SeqRecParams(
        block="olmoe", rank=int(config["hidden_size"]), n_heads=heads,
        head_dim=int(config["hidden_size"]) // heads,
        n_layers=int(config["num_hidden_layers"]), norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]), positions="rope",
        rope_theta=float(config["rope_theta"]),
        tied=bool(config["tie_word_embeddings"]),
        vocab_rows=int(config["vocab_size"]),
        n_experts=int(config["num_experts"]),
        expert_width=int(config["intermediate_size"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        max_seq_len=int(config["max_position_embeddings"]),
        num_steps=int(tr["numSteps"]), batch_size=int(tr["stepRows"]),
        micro_rows=int(tr["microRows"]), encode_rows=int(tr["encodeRows"]),
        compute_dtype=str(tr["computeDtype"]),
        learning_rate=float(tr["learningRate"]),
        n_negatives=int(tr["nNegatives"]), lb_coef=float(tr["lbCoef"]),
        z_coef=float(tr["zCoef"]), seed=int(seed))


def oracle_cfg(params) -> Dict[str, Any]:
    """What ``oracle_seq`` needs of the block."""
    return {"n_layers": params.n_layers, "n_heads": params.n_heads,
            "head_dim": params.head_dim, "norm_eps": params.norm_eps,
            "rope_theta": params.rope_theta,
            "experts_per_token": params.experts_per_token,
            "lb_coef": params.lb_coef, "z_coef": params.z_coef}


def event_columns(st: data.Structure, seed: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user index, item index, time) of every event: the structure's
    events, each user's put in an order drawn from ``seed`` (time =
    the event's place in that order)."""
    rng = np.random.default_rng([int(seed), 7])
    rows = st.rows()
    order = np.lexsort((rng.random(st.n_events), rows))
    times = (np.arange(st.n_events) - st.starts[rows]).astype(np.float64)
    return rows, st.cols[order], times


def training_data(st: data.Structure, seed: int):
    """The template's ``SequenceTrainingData`` (entity ids as the event
    store hands them over: strings) and the users' index sequences the
    oracle reads."""
    from predictionio_tpu.templates.sequentialrec.engine import (
        SequenceTrainingData,
    )

    rows, cols, times = event_columns(st, seed)
    u_labels = np.char.add("u", np.arange(st.n_users).astype(str))
    i_labels = np.char.add("i", np.arange(st.n_items).astype(str))
    return SequenceTrainingData(u_labels[rows], i_labels[cols], times), \
        (rows, cols)
