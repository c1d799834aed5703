"""Seeded data for the cells: the rating structure, factor tables, the
model objects the templates serve, and the engine instance that hands
them to the program's normal deploy path.

Two seeds. ``data_seed`` (from the configuration file) fixes WHICH
(user, item) pairs exist, hence every table shape, the seen bitmap and
every compile-cache key; ``--seed`` draws the values (ratings, factors,
ALS init) on top of that structure, so a new ``--seed`` never compiles.

The structure follows the power laws of ``bench.py::synthetic_ratings``
(item popularity rank^-0.8, user activity rank^-0.6). It is drawn as
per-user counts (one multinomial) plus one inverse-CDF draw per event,
which yields the events already grouped by user: the same law, without
the 20M-element argsort the row-by-row draw needs afterwards.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

ITEM_EXPONENT = 0.8
USER_EXPONENT = 0.6


def power_law_p(n: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    return p / p.sum()


@dataclasses.dataclass
class Structure:
    """Events grouped by user: user ``u`` owns ``cols[starts[u]:starts[u
    + 1]]`` (item indices, duplicates possible, as in an event log)."""

    n_users: int
    n_items: int
    counts: np.ndarray   # int64 [n_users]
    starts: np.ndarray   # int64 [n_users + 1]
    cols: np.ndarray     # int64 [n_events]

    @property
    def n_events(self) -> int:
        return int(self.cols.shape[0])

    def rows(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_users, dtype=np.int64),
                         self.counts)

    def user_items(self, u: int) -> np.ndarray:
        return self.cols[self.starts[u]:self.starts[u + 1]]

    def seen(self) -> Dict[int, np.ndarray]:
        """The ``{user idx: item idx array}`` dict the models carry
        (views into ``cols``; users without events have no entry, as
        the two-stage preparator leaves them)."""
        s, cols = self.starts, self.cols
        return {u: cols[s[u]:s[u + 1]]
                for u in np.flatnonzero(self.counts).tolist()}


def draw_structure(shape: Mapping[str, Any]) -> Structure:
    n_users, n_items = int(shape["n_users"]), int(shape["n_items"])
    n_events = int(shape["n_events"])
    rng = np.random.default_rng(int(shape["data_seed"]))
    counts = rng.multinomial(
        n_events, power_law_p(n_users, USER_EXPONENT)).astype(np.int64)
    cdf = np.cumsum(power_law_p(n_items, ITEM_EXPONENT))
    cols = np.empty(n_events, dtype=np.int64)
    step = 1 << 22
    for lo in range(0, n_events, step):
        u = rng.random(min(step, n_events - lo))
        cols[lo:lo + len(u)] = np.searchsorted(cdf, u, side="right")
    np.minimum(cols, n_items - 1, out=cols)
    starts = np.zeros(n_users + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    return Structure(n_users, n_items, counts, starts, cols)


def entity_maps(n_users: int, n_items: int):
    """Users are named ``u<index>`` and items ``i<index>`` everywhere
    (schedule, oracle, batch files)."""
    from predictionio_tpu.data.bimap import StringIndexBiMap

    def labels(prefix: str, n: int) -> np.ndarray:
        return np.char.add(prefix, np.arange(n).astype(str)).astype(object)

    return (StringIndexBiMap.from_distinct(labels("u", n_users)),
            StringIndexBiMap.from_distinct(labels("i", n_items)))


def rating_values(n_events: int, seed: int) -> np.ndarray:
    """Ratings 1..5, as ``synthetic_ratings`` draws them."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(1, 6, size=n_events).astype(np.float32)


def factor_tables(shapes: Sequence[Tuple[int, int]], seed: int,
                  stream: int = 0) -> List[np.ndarray]:
    """Seeded float32 tables, one jitted device call for all of them
    (normal / sqrt(width): dot products of order one, like trained
    factors), fetched to the host because the model objects the deploy
    path unpickles are host numpy. ``stream`` tells one model's tables
    from another's under the same ``--seed``."""
    import jax
    import jax.numpy as jnp

    shapes = tuple((int(n), int(r)) for n, r in shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shapes))
        return tuple(
            jax.random.normal(k, s, dtype=jnp.float32) / np.sqrt(s[1])
            for k, s in zip(keys, shapes))

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(stream))
    return [np.asarray(t) for t in draw(key)]


# -- the way in: a completed engine instance in an in-memory store ---------

def memory_storage() -> None:
    """METADATA / MODELDATA / EVENTDATA on in-memory DAOs: what
    ``run_train`` leaves behind, without a database in the set-up."""
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.storage import StorageConfig

    storage.reset(StorageConfig(
        sources={"BENCH": {"type": "memory"}},
        repositories={"METADATA": "BENCH", "EVENTDATA": "BENCH",
                      "MODELDATA": "BENCH"}))


def persist_instance(engine_factory: str, engine_params: Any,
                     models: Sequence[Any]) -> str:
    """Write ``models`` as a COMPLETED engine instance, exactly the two
    records ``workflow/core_workflow.py::run_train`` writes, so that
    ``QueryServer.deploy()`` / ``BatchPredictor.load()`` resolve and
    load it through ``build_deployment`` as they would a trained one."""
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.storage.base import Model
    from predictionio_tpu.workflow.core_workflow import serialize_models
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig,
        new_engine_instance,
    )

    instance = new_engine_instance(
        WorkflowConfig(engine_factory=engine_factory), engine_params)
    instances = storage.get_metadata_engine_instances()
    iid = instances.insert(instance)
    storage.get_model_data_models().insert(
        Model(id=iid, models=serialize_models(list(models))))
    instances.update(dataclasses.replace(
        instances.get(iid), status="COMPLETED",
        end_time=_dt.datetime.now(tz=_dt.timezone.utc)))
    return iid


@dataclasses.dataclass
class ServingState:
    """What a serving cell's oracle needs: the seeded tables on the
    host, rounded to the store's stated precision, and the structure
    that says who has seen what."""

    structure: Structure
    user_factors: np.ndarray
    item_factors: np.ndarray
    stage2_users: Optional[np.ndarray] = None
    stage2_items: Optional[np.ndarray] = None
    candidates: int = 0
    store_bytes: int = 2

    def work(self, kind: str, rank: int) -> Dict[str, Any]:
        """What the roofline readers need of the cell's shapes."""
        return {"kind": kind, "n_items": self.structure.n_items,
                "rank": rank, "store_bytes": self.store_bytes,
                "stage2_width": 0 if self.stage2_items is None
                else int(self.stage2_items.shape[1]),
                "candidates": self.candidates}


def build_serving_instance(config: Mapping[str, Any], seed: int,
                           spans: Dict[str, float]) -> ServingState:
    """Seeded models for ``config`` persisted as an engine instance.
    The models come from ``benchmark/models/<template>.py`` and the
    oracle's rounding from ``benchmark/stores/<precision>.py``, each
    found by the name the configuration states, so a new template or
    store precision is a new file. ``spans`` receives the seconds of
    each part (set-up breakdown)."""
    import importlib
    import time

    precision = config["store"]["precision"]
    if config["env"].get("PIO_SERVE_PRECISION") != precision:
        raise ValueError(
            f"the configuration states a {precision} store but sets "
            f"PIO_SERVE_PRECISION={config['env'].get('PIO_SERVE_PRECISION')}")
    store = importlib.import_module(f"benchmark.stores.{precision}")
    build = importlib.import_module(
        f"benchmark.models.{config['template']}").build
    t = time.perf_counter()
    st = draw_structure(config["shape"])
    spans["draw_structure_s"] = time.perf_counter() - t
    t = time.perf_counter()
    user_map, item_map = entity_maps(st.n_users, st.n_items)
    seen = st.seen()
    spans["maps_seen_s"] = time.perf_counter() - t
    t = time.perf_counter()
    # the models carry the unrounded float32 tables, as a trainer would
    # hand them over, and the program casts them; the oracle's copies
    # are in the store's stated precision (see harness/oracle.py)
    models, params, tables = build(config, st, user_map, item_map, seen,
                                   int(seed))
    spans["factor_tables_s"] = time.perf_counter() - t
    state = ServingState(
        st, **{k: store.round_table(v) for k, v in tables.items()},
        candidates=int(config.get("two_stage", {}).get("candidates", 0)),
        store_bytes=int(store.BYTES_PER_ELEMENT))
    t = time.perf_counter()
    persist_instance(config["engine_factory"], params, models)
    spans["persist_instance_s"] = time.perf_counter() - t
    return state
