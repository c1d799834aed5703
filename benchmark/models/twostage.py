"""The two-stage template's models: the recommendation template's
``ALSModel`` as stage 1 and a ``SeqRecModel`` as stage 2. The encoder
does not run at query time (user vectors are precomputed when the model
is trained), so theta holds only the tied item table, which IS the
stage-2 item table."""

from typing import Any, Dict, Tuple

from benchmark.harness import data
from benchmark.models import recommendation


def build(config, st, user_map, item_map, seen, seed: int
          ) -> Tuple[list, Any, Dict[str, Any]]:
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.ops.seqrec import SeqRecParams
    from predictionio_tpu.templates.sequentialrec.engine import (
        DataSourceParams,
        SeqRecModel,
    )

    two = config["two_stage"]
    width = int(two["width"])
    als, als_params, tables = recommendation.als(
        config, st, user_map, item_map, seen, seed)
    X2, Y2 = data.factor_tables([(st.n_users, width), (st.n_items, width)],
                                seed, stream=1)
    enc = SeqRecParams(rank=width, n_layers=int(two["n_layers"]),
                       n_heads=int(two["n_heads"]),
                       max_seq_len=int(two["max_seq_len"]))
    seq = SeqRecModel(X2, Y2, user_map, item_map, seen, {"item_emb": Y2},
                      enc, int(two["max_seq_len"]))
    tables.update(stage2_users=X2, stage2_items=Y2)
    return [als, seq], EngineParams(
        data_source_params=("", DataSourceParams(app_name="bench")),
        algorithm_params_list=[("als", als_params), ("seqrec", enc)]), tables
