"""The recommendation template's model: one ``ALSModel`` holding the
seeded user and item factors."""

from typing import Any, Dict, Tuple

from benchmark.harness import data


def als(config, st, user_map, item_map, seen, seed: int):
    """(``ALSModel``, its ``ALSParams``, its float32 tables)."""
    from predictionio_tpu.ops.als import ALSParams
    from predictionio_tpu.templates.recommendation.engine import ALSModel

    rank = int(config["shape"]["rank"])
    X, Y = data.factor_tables([(st.n_users, rank), (st.n_items, rank)],
                              seed)
    return (ALSModel(X, Y, user_map, item_map, seen),
            ALSParams(rank=rank, num_iterations=1, seed=int(seed)),
            {"user_factors": X, "item_factors": Y})


def build(config, st, user_map, item_map, seen, seed: int
          ) -> Tuple[list, Any, Dict[str, Any]]:
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.templates.recommendation.engine import (
        DataSourceParams,
    )

    model, params, tables = als(config, st, user_map, item_map, seen, seed)
    return [model], EngineParams(
        data_source_params=("", DataSourceParams(app_name="bench")),
        algorithm_params_list=[("als", params)]), tables
