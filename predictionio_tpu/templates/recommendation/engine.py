"""Recommendation engine: rate events -> implicit ALS -> top-N items.

Capability parity with ``examples/scala-parallel-recommendation`` (the
driver's north-star workload, BASELINE.md):

- DataSource reads ``rate``/``view`` events via PEventStore
  (``custom-query/src/main/scala/DataSource.scala:31-65``)
- Preparator indexes entity IDs with BiMap and pads ratings into the
  TPU layout (``Preparator.scala`` + BiMap.scala:63-129)
- ALSAlgorithm trains implicit ALS on the mesh
  (``ALSAlgorithm.scala:64-103``: rank/iters/lambda/seed, alpha=1.0)
- predict: per-user dot-product top-N with optional seen-item blacklist;
  item-similarity cosine scoring available for item queries
- Serving returns the first algorithm's result

The model is a P2L product: factors come back to host numpy and pickle
cleanly into the Models repository (persistence mode 1).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.controller import (
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    LFirstServing,
    LServing,
    OptionAverageMetric,
    P2LAlgorithm,
    PAlgorithm,
    Params,
    PDataSource,
    PPreparator,
)
from predictionio_tpu.core.context import ComputeContext
from predictionio_tpu.data.bimap import BiMap, StringIndexBiMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.als import (
    ALSParams,
    BucketedRatings,
    bucket_ratings_pair,
)


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    """``streaming_block_size`` switches the read to the scale-ingest
    path: columnar blocks streamed through an incremental indexer, so a
    10–20M-rating store is never materialized as whole-store object
    columns (SURVEY hard part #2); None keeps the single-scan read."""

    app_name: str
    event_names: Tuple[str, ...] = ("rate",)
    channel_name: Optional[str] = None
    streaming_block_size: Optional[int] = None
    # pipelined flavor of the streaming read: per-block sort while
    # decode runs, merge-based finalize (identical training inputs,
    # see data/columnar.PipelinedRatingsBuilder); decode_prefetch is
    # passed to the backend as its read-ahead hint (jsonlfs decodes
    # that many partitions in parallel)
    pipelined_ingest: bool = False
    decode_prefetch: int = 0
    # filter-by-category variant: also aggregate item $set categories so
    # queries can restrict recommendations to categories
    # (filter-by-category/.../DataSource.scala:60-79)
    read_item_categories: bool = False
    # sliding-window evaluation (the mlc movielens-evaluation example's
    # EventsSlidingEvalParams: firstTrainingUntilTime / evalDuration /
    # evalCount): eval set k trains on events before
    # first_until + k*duration and tests on the following window.
    # eval_count = 0 keeps the default leave-last-out protocol.
    eval_first_until: Optional[str] = None   # ISO-8601
    eval_duration_days: float = 7.0
    eval_count: int = 0


@dataclasses.dataclass
class Rating:
    user: str
    item: str
    rating: float


class TrainingData:
    """Columnar rating triples (users/items as object arrays, float32
    values) — the TPU ingest format. Accepts a ``Rating`` list for parity
    with the reference template's ``TrainingData(ratings: RDD[Rating])``
    (``DataSource.scala:62-65``); ``.ratings`` materializes lazily."""

    def __init__(self, ratings: Optional[List[Rating]] = None, *,
                 users: Optional[np.ndarray] = None,
                 items: Optional[np.ndarray] = None,
                 values: Optional[np.ndarray] = None):
        if ratings is not None:
            n = len(ratings)
            users = np.asarray([r.user for r in ratings], dtype=object)
            items = np.asarray([r.item for r in ratings], dtype=object)
            values = np.fromiter((r.rating for r in ratings),
                                 dtype=np.float32, count=n)
        self.users = users if users is not None \
            else np.empty(0, dtype=object)
        self.items = items if items is not None \
            else np.empty(0, dtype=object)
        self.values = values if values is not None \
            else np.empty(0, dtype=np.float32)
        if not (len(self.users) == len(self.items) == len(self.values)):
            raise ValueError(
                f"misaligned rating columns: {len(self.users)} users, "
                f"{len(self.items)} items, {len(self.values)} values")
        self.item_categories: Optional[Dict[str, Tuple[str, ...]]] = None
        # a None id would become the literal string 'None' at indexing time
        # and train a phantom row/column (cf. ColumnarEvents.encode_entities)
        for name, col in (("user", self.users), ("item", self.items)):
            missing = np.fromiter((x is None for x in col), dtype=bool,
                                  count=len(col))
            if missing.any():
                raise ValueError(
                    f"TrainingData has events without a {name} id; filter "
                    "the event scan (e.g. by target_entity_type)")
        self._ratings: Optional[List[Rating]] = ratings

    @property
    def ratings(self) -> List[Rating]:
        if self._ratings is None:
            self._ratings = [
                Rating(str(u), str(i), float(v))
                for u, i, v in zip(self.users, self.items, self.values)]
        return self._ratings

    def __len__(self) -> int:
        return int(self.users.shape[0])

    def sanity_check(self) -> None:
        assert len(self), (
            "ratings in TrainingData cannot be empty. Please check if "
            "DataSource generates TrainingData correctly.")


def _training_data_prechecked(users: np.ndarray, items: np.ndarray,
                              values: np.ndarray) -> "TrainingData":
    """TrainingData from columns ALREADY validated for None ids —
    sliding eval slices one validated batch per window and must not
    re-pay the O(n) scan eval_count times."""
    td = TrainingData.__new__(TrainingData)
    td.users = users
    td.items = items
    td.values = values
    td.item_categories = None
    td._ratings = None
    return td


class IndexedTrainingData:
    """Already-indexed rating triples from the streaming ingest: dense
    int64 user/item codes plus their BiMaps. The Preparator recognizes
    this and skips re-indexing (the whole point — the string columns
    were never materialized)."""

    def __init__(self, user_map: StringIndexBiMap,
                 item_map: StringIndexBiMap, rows: np.ndarray,
                 cols: np.ndarray, values: np.ndarray):
        self.user_map = user_map
        self.item_map = item_map
        self.rows = rows
        self.cols = cols
        self.values = values
        self.item_categories: Optional[Dict[str, Tuple[str, ...]]] = None

    def __len__(self) -> int:
        return int(self.rows.shape[0])

    def sanity_check(self) -> None:
        assert len(self), (
            "ratings in TrainingData cannot be empty. Please check if "
            "DataSource generates TrainingData correctly.")


class EventDataSource(PDataSource):
    """Reads rating events (DataSource.scala:31-65): rate -> property
    'rating', view -> implicit count of 1. Uses the columnar bulk-read
    path so no per-event Python objects are built; with
    ``streaming_block_size`` set, the read streams bounded blocks
    through an incremental indexer (the partitioned-read analog of
    JDBCPEvents.scala:31-100)."""

    params_class = DataSourceParams

    def read_training(self, ctx: ComputeContext) -> Any:
        return self._read_training(pipelined=None)

    def _read_training(self, pipelined: Optional[bool]) -> Any:
        """``pipelined=None`` follows params; ``False`` forces the
        serial builder (read_eval: its leave-last-out split consumes
        RAW triple order without dedup, and the pipelined finalize
        returns merged (row, col) order — eval must see the same
        stream order as the serial path)."""
        p: DataSourceParams = self.params
        if p.pipelined_ingest and not p.streaming_block_size:
            raise ValueError(
                "pipelined_ingest requires streaming_block_size: the "
                "pipelined builder consumes streamed columnar blocks "
                "(set datasource {\"streamingBlockSize\": N} alongside "
                "\"pipelinedIngest\": true)")
        if pipelined is None:
            pipelined = bool(p.pipelined_ingest)
        if p.streaming_block_size:
            from predictionio_tpu.data.columnar import (
                PipelinedRatingsBuilder,
                StreamingRatingsBuilder,
                iter_blocks_threaded,
            )

            builder = (PipelinedRatingsBuilder() if pipelined
                       else StreamingRatingsBuilder())
            # decode thread + indexing consumer overlap (bounded queue)
            for block in iter_blocks_threaded(
                    PEventStore.find_columnar_blocks(
                        app_name=p.app_name,
                        channel_name=p.channel_name,
                        entity_type="user",
                        event_names=list(p.event_names),
                        target_entity_type="item",
                        value_property="rating",
                        default_value=1.0,
                        block_size=int(p.streaming_block_size),
                        prefetch=int(p.decode_prefetch))):
                builder.add_block(block)
            td = IndexedTrainingData(*builder.finalize())
            td.item_categories = self._read_item_categories(p)
            return td
        batch = PEventStore.find_columnar(
            app_name=p.app_name,
            channel_name=p.channel_name,
            entity_type="user",
            event_names=list(p.event_names),
            target_entity_type="item",
            value_property="rating",
            default_value=1.0,
        )
        td = TrainingData(users=batch.entity_ids, items=batch.target_ids,
                          values=batch.values)
        td.item_categories = self._read_item_categories(p)
        return td

    @staticmethod
    def _read_item_categories(p: DataSourceParams):
        """$set item categories (filter-by-category DataSource.scala:
        60-79); None when the variant flag is off."""
        if not p.read_item_categories:
            return None
        return {
            iid: tuple(pm.get_opt("categories", list) or ())
            for iid, pm in PEventStore.aggregate_properties(
                app_name=p.app_name, channel_name=p.channel_name,
                entity_type="item").items()
        }

    def read_eval(self, ctx: ComputeContext):
        """Default: leave-last-out per user (readEval analog in the
        template's evaluation variant). With ``eval_count`` > 0:
        time-sliding windows (train on everything before the cut, test
        on the next window — EventsSlidingEvalParams semantics from the
        reference's movielens-evaluation example)."""
        p: DataSourceParams = self.params
        if p.eval_count > 0:
            return self._sliding_eval(p)
        # serial builder even under pipelined_ingest: leave-last-out
        # splits on raw triple ORDER, which the pipelined finalize
        # does not preserve (merged (row, col) order)
        from predictionio_tpu.data.sliding import leave_last_out

        td = self._read_training(pipelined=False)
        if isinstance(td, IndexedTrainingData):
            # eval works on typed ratings; decode the streamed triples
            td = TrainingData(users=td.user_map.decode(td.rows),
                              items=td.item_map.decode(td.cols),
                              values=td.values)
        by_user: Dict[str, List[Rating]] = {}
        for r in td.ratings:
            by_user.setdefault(r.user, []).append(r)
        train, holdouts = leave_last_out(by_user)
        qa = [(Query(user=user, num=10), ActualResult([held.item]))
              for user, held in holdouts]
        return [(TrainingData(train), EmptyEvalInfo(), qa)]

    def _sliding_eval(self, p: DataSourceParams):
        """Sliding time windows: for k in range(eval_count), train on
        events before ``first_until + k*duration`` and hold out each
        user's items in the following window as actuals."""
        import datetime as _dt

        from predictionio_tpu.data.event import _parse_time

        if not p.eval_first_until:
            raise ValueError(
                "eval_count > 0 requires eval_first_until (ISO-8601)")
        if p.streaming_block_size:
            raise ValueError(
                "sliding-window eval materializes the scanned window and "
                "is incompatible with streaming_block_size; drop one of "
                "the two (the scan is bounded to the eval horizon)")
        from predictionio_tpu.data.sliding import sliding_window_masks

        first_until = _parse_time(p.eval_first_until)
        t0 = first_until.timestamp()
        dur = float(p.eval_duration_days) * 86400.0
        horizon = first_until + _dt.timedelta(
            seconds=dur * int(p.eval_count))
        # the scan never needs events past the last test window
        batch = PEventStore.find_columnar(
            app_name=p.app_name, channel_name=p.channel_name,
            entity_type="user", event_names=list(p.event_names),
            target_entity_type="item", value_property="rating",
            default_value=1.0, until_time=horizon)
        # validate the id columns ONCE; per-window slices reuse them
        probe = TrainingData(users=batch.entity_ids,
                             items=batch.target_ids, values=batch.values)
        del probe
        times = batch.event_times
        sets = []
        for k, train_mask, test_mask in sliding_window_masks(
                times, t0, dur, int(p.eval_count),
                hint="move eval_first_until later or reduce eval_count"):
            td = _training_data_prechecked(
                batch.entity_ids[train_mask],
                batch.target_ids[train_mask],
                batch.values[train_mask])
            held: Dict[str, List[str]] = {}
            for u, i in zip(batch.entity_ids[test_mask],
                            batch.target_ids[test_mask]):
                held.setdefault(str(u), []).append(str(i))
            qa = [(Query(user=u, num=10), ActualResult(items))
                  for u, items in held.items()]
            sets.append((td, EmptyEvalInfo(), qa))
        return sets


@dataclasses.dataclass(frozen=True)
class EmptyEvalInfo:
    pass


@dataclasses.dataclass(frozen=True)
class Query:
    """Top-N query: by user (personal recs) or by items (similarity)."""

    user: Optional[str] = None
    items: Tuple[str, ...] = ()
    num: int = 10
    blacklist: Tuple[str, ...] = ()
    # filter-by-category variant: only items in these categories
    # (filter-by-category/.../Engine.scala query field)
    categories: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: Tuple[ItemScore, ...]


@dataclasses.dataclass(frozen=True)
class ActualResult:
    items: Tuple[str, ...]

    def __init__(self, items: Sequence[str]):
        object.__setattr__(self, "items", tuple(items))


@dataclasses.dataclass
class PreparedData:
    """BiMap-indexed, TPU-padded ratings."""

    user_map: StringIndexBiMap
    item_map: StringIndexBiMap
    user_side: BucketedRatings
    item_side: BucketedRatings
    seen: Dict[int, np.ndarray]  # user idx -> item idx array (for blacklist)
    # filter-by-category variant: item idx -> categories (None = unread)
    item_categories: Optional[Dict[int, Tuple[str, ...]]] = None

    def sanity_check(self) -> None:
        assert self.user_side.n_rows > 0, "no users after indexing"
        assert self.user_side.n_cols > 0, "no items after indexing"


@dataclasses.dataclass(frozen=True)
class PreparatorParams(Params):
    """``max_len`` bounds the padded row length (keeping the
    largest-magnitude ratings per row); unset, every pair trains (the
    full-RDD semantics of ``ALS.trainImplicit``).

    ``bucketed`` is no longer read: the layout is always length buckets
    (``ops.als.bucket_ratings_pair``). The field goes when the
    benchmark stops passing it (ROADMAP Design 7j)."""

    max_len: Optional[int] = None
    bucketed: bool = False


class RatingsPreparator(PPreparator):
    """BiMap.stringInt indexing + ALX padding (the reference does the BiMap
    step inside ALSAlgorithm.train, ALSAlgorithm.scala:35-36; here it is a
    proper Preparator so multiple algorithms share the layout). Accepts
    either a :class:`TrainingData` (indexes it here) or an
    :class:`IndexedTrainingData` from the streaming ingest (already
    indexed — no whole-store string columns ever existed)."""

    params_class = PreparatorParams

    def prepare(self, ctx: ComputeContext, td: Any) -> PreparedData:
        if isinstance(td, IndexedTrainingData):
            user_map, item_map = td.user_map, td.item_map
            rows = np.asarray(td.rows, dtype=np.int64)
            cols = np.asarray(td.cols, dtype=np.int64)
            vals = np.asarray(td.values, dtype=np.float32)
        else:
            u_labels, rows = np.unique(td.users.astype(str),
                                       return_inverse=True)
            i_labels, cols = np.unique(td.items.astype(str),
                                       return_inverse=True)
            user_map = StringIndexBiMap.from_distinct(u_labels)
            item_map = StringIndexBiMap.from_distinct(i_labels)
            rows = rows.astype(np.int64)
            cols = cols.astype(np.int64)
            vals = np.asarray(td.values, dtype=np.float32)
        n_u, n_i = len(user_map), len(item_map)
        max_len = getattr(self.params, "max_len", None)
        user_side, item_side = bucket_ratings_pair(
            rows, cols, vals, n_u, n_i, max_len=max_len)
        # per-user seen-item lists via one stable sort (vs n_u boolean scans)
        order = np.argsort(rows, kind="stable")
        s_rows, s_cols = rows[order], cols[order]
        starts = np.searchsorted(s_rows, np.arange(n_u))
        ends = np.searchsorted(s_rows, np.arange(n_u), side="right")
        seen = {u: s_cols[starts[u]:ends[u]] for u in range(n_u)}
        cats = None
        raw_cats = getattr(td, "item_categories", None)
        if raw_cats is not None:
            cats = {item_map[iid]: tuple(c)
                    for iid, c in raw_cats.items() if iid in item_map}
        return PreparedData(user_map, item_map, user_side, item_side, seen,
                            item_categories=cats)


class _DeviceServedModel:
    """Shared device-serving plumbing: lazy DeviceTopK construction
    (``_make_server`` is the per-flavor hook) and pickling that drops
    the device handles."""

    _server: Any = None

    def device_server(self):
        if self._server is None:
            self._server = self._make_server()
        return self._server

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_server"] = None  # device handles don't pickle
        # derived caches rebuild on demand; keep model blobs lean
        state.pop("_cat_index", None)
        state.pop("_cat_black_cache", None)
        state.pop("_theta_device", None)  # sequentialrec device cache
        state.pop("_fold_histories", None)  # session fold-in bookkeeping
        state.pop("_folded", None)
        return state


@dataclasses.dataclass
class ALSModel(_DeviceServedModel):
    """Host-persistable factors + maps (ALSModel.scala analog; automatic
    persistence — pickles into the Models repo). Serving runs on the
    DEVICE: ``device_server()`` places the factors in HBM behind an
    AOT-compiled top-k program (ops/serving.py); the pickled blob never
    contains device state."""

    user_factors: np.ndarray     # [N, R]
    item_factors: np.ndarray     # [M, R]
    user_map: StringIndexBiMap
    item_map: StringIndexBiMap
    seen: Dict[int, np.ndarray]
    item_categories: Optional[Dict[int, Tuple[str, ...]]] = None
    _server: Any = dataclasses.field(default=None, repr=False, compare=False)

    def _make_server(self):
        # backend policy: host numpy for small host-resident factors
        # (beats any host<->device transport, the reference's in-JVM
        # predict shape), device program otherwise; override with
        # PIO_SERVING_BACKEND=host|device
        from predictionio_tpu.ops.serving import choose_server

        return choose_server(self.user_factors, self.item_factors, self.seen)

    def sanity_check(self) -> None:
        assert np.isfinite(self.user_factors).all(), "non-finite user factors"
        assert np.isfinite(self.item_factors).all(), "non-finite item factors"


def _coerce_query(query: Any) -> Query:
    """Raw JSON query from the server -> typed Query."""
    if isinstance(query, dict):
        return Query(user=query.get("user"),
                     items=tuple(query.get("items", ())),
                     num=int(query.get("num", 10)),
                     blacklist=tuple(query.get("blacklist", ())),
                     categories=tuple(query.get("categories", ())))
    return query


def _winners_to_result(idx, scores, black, num: int,
                       item_map: StringIndexBiMap,
                       positive_only: bool = True) -> PredictedResult:
    """Fetched top-k row -> PredictedResult: drop blacklisted, non-finite
    and (for ALS-style scorers) non-positive scores host-side, clip to
    num. ``math.isfinite`` on the python floats, not ``np.isfinite`` per
    element — this runs once per query of a bulk batch-predict job.

    ``positive_only=False`` keeps negative finite scores: transformer
    logits (the sequentialrec template) are only RELATIVELY calibrated,
    so a user whose unseen-item dot products are all negative still has
    a valid ranking — only the ``-inf`` device masks (padding / seen
    items) must drop. Models opt out via ``serve_positive_scores_only
    = False``; implicit-ALS keeps the historical positive filter."""
    keep = [(i, s) for i, s in zip(idx.tolist(), scores.tolist())
            if i not in black and math.isfinite(s)
            and (s > 0 or not positive_only)][:num]
    if not keep:
        return PredictedResult(())
    items = item_map.decode(np.asarray([i for i, _ in keep],
                                       dtype=np.int64))
    return PredictedResult(tuple(
        ItemScore(item=item, score=s)
        for item, (_, s) in zip(items, keep)))


_CAT_BLACKLIST_CACHE_MAX = 64
_cat_cache_lock = threading.Lock()


def _category_blacklist(model, categories: Tuple[str, ...]) -> set:
    """Item indices OUTSIDE the requested categories (filter-by-category
    ALSAlgorithm.scala:85-101: recommendations restricted to the query
    categories; items without categories are out). The inverted
    category index and the per-categories complement are cached on the
    model — the serving hot path must not pay an O(n_items) Python loop
    per query. The complement cache is a bounded LRU: each entry is
    O(n_items), and a public endpoint can present unboundedly many
    distinct category combinations. Mutations take a lock — the query
    server serves on concurrent threads (ThreadingHTTPServer)."""
    import collections

    with _cat_cache_lock:
        cache = getattr(model, "_cat_black_cache", None)
        if cache is None:
            cache = collections.OrderedDict()
            model._cat_black_cache = cache
        black = cache.get(categories)
        if black is not None:
            cache.move_to_end(categories)
            return black
    index = getattr(model, "_cat_index", None)
    if index is None:
        index = {}
        for ix, cats in model.item_categories.items():
            for c in cats:
                index.setdefault(c, set()).add(ix)
        model._cat_index = index
    eligible: set = set()
    for c in categories:
        eligible |= index.get(c, set())
    black = set(range(len(model.item_map))) - eligible
    with _cat_cache_lock:
        cache[categories] = black
        while len(cache) > _CAT_BLACKLIST_CACHE_MAX:
            cache.popitem(last=False)
    return black


def _serve_topk(server, model, query: Query) -> PredictedResult:
    """Shared device-serving logic for both ALS flavors: ask the compiled
    program for num + |blacklist| winners (seen items already masked on
    device), drop blacklisted/non-positive ones host-side, clip to num.
    A category restriction joins the blacklist (with a full ranking, so
    enough in-category candidates survive the cut)."""
    user_map, item_map = model.user_map, model.item_map
    black = {item_map[i] for i in query.blacklist if i in item_map}
    if query.categories:
        if getattr(model, "item_categories", None) is None:
            raise ValueError(
                "query has categories but the model was trained without "
                "read_item_categories=True on the datasource")
        black = black | _category_blacklist(model, query.categories)
    k = query.num + len(black)
    if query.items:
        idxs = [item_map[i] for i in query.items if i in item_map]
        if not idxs:
            return PredictedResult(())
        idx, scores = server.items_topk(idxs, k)
    elif query.user is not None:
        uidx = user_map.get(query.user)
        if uidx is None:
            return PredictedResult(())
        idx, scores = server.user_topk(uidx, k)
    else:
        return PredictedResult(())
    return _winners_to_result(
        idx, scores, black, query.num, item_map,
        positive_only=getattr(model, "serve_positive_scores_only", True))


class _DeviceServingAlgo:
    """Shared predict/warmup for every ALS flavor served by DeviceTopK."""

    def warmup_base(self, model) -> None:
        """Compile the device top-k buckets at deploy so the first real
        query pays no compile/first-dispatch cost (SURVEY hard part #4)."""
        if len(model.user_map):
            model.device_server().warmup()

    def predict(self, model, query: Query) -> PredictedResult:
        query = _coerce_query(query)
        return _serve_topk(model.device_server(), model, query)

    def _batched_predict(self, model, indexed_queries
                         ) -> List[Tuple[int, Any]]:
        """Batch-predict as ONE device job (P2LAlgorithm.scala:66-68):
        known-user queries are grouped per (num + blacklist) bucket and
        dispatched through `DeviceTopK.users_topk` — one round trip per
        group instead of one per query; item-similarity / unknown-user
        queries fall back to the per-query path."""
        queries = [(qx, _coerce_query(q)) for qx, q in indexed_queries]
        server = model.device_server()
        results: Dict[int, Any] = {}
        # (k needed) -> list of (qx, uidx, blacklist idx set, num)
        groups: Dict[int, List[Tuple[int, int, set, int]]] = {}
        for qx, q in queries:
            # category queries need the full-ranking path in predict()
            uidx = (model.user_map.get(q.user)
                    if q.user is not None and not q.items
                    and not q.categories else None)
            if uidx is None:
                results[qx] = self.predict(model, q)
                continue
            black = {model.item_map[i] for i in q.blacklist
                     if i in model.item_map}
            k = q.num + len(black)
            groups.setdefault(k, []).append((qx, uidx, black, q.num))
        for k, rows in groups.items():
            uids = np.asarray([r[1] for r in rows], dtype=np.int64)
            idx, scores = server.users_topk(uids, k)
            positive = getattr(model, "serve_positive_scores_only", True)
            for row, (qx, _, black, num) in enumerate(rows):
                results[qx] = _winners_to_result(
                    idx[row], scores[row], black, num, model.item_map,
                    positive_only=positive)
        return [(qx, results[qx]) for qx, _ in queries]


class ALSAlgorithm(_DeviceServingAlgo, P2LAlgorithm):
    """Implicit ALS on the TPU mesh (ALSAlgorithm.scala:64-103 parity)."""

    params_class = ALSParams
    query_cls = Query

    def train(self, ctx: ComputeContext, pd: PreparedData) -> ALSModel:
        # topology-aware: sharded over the (multi-host) mesh when one
        # exists, single-device otherwise (parallel/als_sharding.py)
        from predictionio_tpu.parallel.als_sharding import train_als_auto
        from predictionio_tpu.workflow import runlog
        from predictionio_tpu.workflow.checkpoint import (
            bimap_fingerprint_scope)

        # the entity maps join the crash-safe checkpoint fingerprint:
        # two stores with identical table shapes but different entity
        # universes must never resume each other's checkpoints
        # (no-op while checkpointing is off); the run-context scope
        # stamps the run-history header so `pio runs list` can say
        # WHAT trained, not just when
        with bimap_fingerprint_scope(pd.user_map, pd.item_map), \
                runlog.run_context_scope(
                    template="recommendation",
                    nUsers=pd.user_side.n_rows,
                    nItems=pd.user_side.n_cols):
            X, Y = train_als_auto(pd.user_side, pd.item_side, self.params)
        return ALSModel(X, Y, pd.user_map, pd.item_map, pd.seen,
                        item_categories=pd.item_categories)

    def batch_predict(self, ctx: ComputeContext, model: "ALSModel",
                      indexed_queries) -> List[Tuple[int, Any]]:
        return self._batched_predict(model, indexed_queries)


@dataclasses.dataclass
class ShardedALSModel(_DeviceServedModel):
    """Device-RESIDENT model: factor matrices live sharded in HBM
    (padded jax Arrays from ``train_als_device``) and are never gathered
    to host — the PAlgorithm 'model bigger than a host' semantics
    (PAlgorithm.scala:24-45, SURVEY hard part #5). Not picklable by
    design; persistence mode is RETRAIN-at-deploy."""

    user_factors: Any            # jax Array [N_pad, R], sharded
    item_factors: Any            # jax Array [M_pad, R], sharded
    n_users: int
    n_items: int
    user_map: StringIndexBiMap
    item_map: StringIndexBiMap
    seen: Dict[int, np.ndarray]
    item_categories: Optional[Dict[int, Tuple[str, ...]]] = None
    # density-aware shard layout (parallel.als_sharding.ItemShardLayout)
    # carried WITH the model so serving, fold-in, and eval all see one
    # consistent item placement; None serves the training placement
    item_layout: Any = None
    _server: Any = dataclasses.field(default=None, repr=False, compare=False)

    def _make_server(self):
        from predictionio_tpu.ops.serving import DeviceTopK

        return DeviceTopK(
            self.user_factors, self.item_factors, self.seen,
            n_users=self.n_users, n_items=self.n_items,
            item_layout=self.item_layout)

    def sanity_check(self) -> None:
        # finiteness check WITHOUT gathering the factors: reduce on device
        import jax.numpy as jnp

        assert bool(jnp.isfinite(self.user_factors).all()), \
            "non-finite user factors"
        assert bool(jnp.isfinite(self.item_factors).all()), \
            "non-finite item factors"


class ALSShardedAlgorithm(_DeviceServingAlgo, PAlgorithm):
    """PAlgorithm flavor of the ALS template: trains with
    ``train_als_device`` and serves straight from the HBM shards through
    the compiled top-k program — no host copy of the factors exists at
    any point (the reference's RDD-model ALS variant,
    ``examples/scala-parallel-recommendation/custom-query/.../
    ALSAlgorithm.scala:77-103``, where predict runs cluster-side)."""

    params_class = ALSParams
    query_cls = Query

    def train(self, ctx: ComputeContext,
              pd: PreparedData) -> ShardedALSModel:
        import jax

        from predictionio_tpu.ops.als import item_interaction_counts
        from predictionio_tpu.parallel.als_sharding import (
            density_aware_item_layout,
            train_als_device,
        )
        from predictionio_tpu.workflow import runlog
        from predictionio_tpu.workflow.checkpoint import (
            bimap_fingerprint_scope)

        with bimap_fingerprint_scope(pd.user_map, pd.item_map), \
                runlog.run_context_scope(
                    template="recommendation-sharded",
                    nUsers=pd.user_side.n_rows,
                    nItems=pd.user_side.n_cols):
            X, Y = train_als_device(pd.user_side, pd.item_side,
                                    self.params)
        # serving layout: on a multi-device runtime the item store
        # re-places density-aware (greedy bin-pack over the power-law
        # head, ISSUE 15) so no serve shard hot-spots; the layout
        # travels inside the model so fold-in/eval read one placement
        layout = None
        n_dev = len(jax.devices())
        if n_dev > 1:
            layout = density_aware_item_layout(
                item_interaction_counts(pd.item_side), n_dev)
        return ShardedALSModel(
            X, Y, pd.user_side.n_rows, pd.user_side.n_cols,
            pd.user_map, pd.item_map, pd.seen,
            item_categories=pd.item_categories, item_layout=layout)

    def batch_predict(self, ctx: ComputeContext, model: ShardedALSModel,
                      indexed_queries) -> List[Tuple[int, Any]]:
        """Evaluation over the device-resident model: the whole query set
        runs as grouped `users_topk` dispatches against the HBM shards —
        one round trip per group, not per query."""
        return self._batched_predict(model, indexed_queries)


class RecommendationServing(LFirstServing):
    """First-serving (template Serving.scala returns the single result)."""


@dataclasses.dataclass(frozen=True)
class ServingParams(Params):
    """custom-serving variant (its Serving.scala:10): path of a file
    listing disabled product ids, one per line."""

    filepath: str = "disabled.txt"


class FileBlacklistServing(LServing):
    """custom-serving variant: re-read the disabled-products file on
    EVERY query (deliberate in the reference — ops can edit the file
    under a live server) and drop those items from the first
    algorithm's result (custom-serving/.../Serving.scala:13-27)."""

    params_class = ServingParams

    def serve(self, query: Query,
              predictions: List[PredictedResult]) -> PredictedResult:
        import os

        filepath = getattr(self.params, "filepath", "disabled.txt")
        disabled = set()
        if os.path.exists(filepath):
            with open(filepath, "r", encoding="utf-8") as f:
                disabled = {ln.strip() for ln in f if ln.strip()}
        head = predictions[0]
        return PredictedResult(tuple(
            s for s in head.item_scores if s.item not in disabled))


class PrecisionAtK(OptionAverageMetric):
    """Precision@k on top-N recommendations — the BASELINE.md quality
    parity metric (mirrors the reference's movielens evaluation example,
    ``examples/experimental/scala-parallel-recommendation-mlc/``): for
    each (query, predicted, actual), the fraction of the top-k
    recommended items that appear in the held-out actuals; None (skipped)
    when the user has no actuals."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"Precision@{self.k}"

    def calculate_qpa(self, q: Query, p: PredictedResult,
                      a: ActualResult) -> Optional[float]:
        if not a.items:
            return None
        actual = set(a.items)
        top = [s.item for s in p.item_scores[:self.k]]
        if not top:
            return 0.0
        return sum(1 for i in top if i in actual) / float(self.k)


class NDCGAtK(OptionAverageMetric):
    """NDCG@k on top-N recommendations — the sequence-aware companion
    to :class:`PrecisionAtK` (ROADMAP item-1 follow-on): rank position
    matters, so a model that puts a held-out item first scores higher
    than one that buries it at position k. Shares the binary-relevance
    math with the bench (``data.sliding.ndcg_at_k``)."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"NDCG@{self.k}"

    def calculate_qpa(self, q: Query, p: PredictedResult,
                      a: ActualResult) -> Optional[float]:
        if not a.items:
            return None
        from predictionio_tpu.data.sliding import ndcg_at_k

        return ndcg_at_k([s.item for s in p.item_scores], a.items,
                         self.k)


class RecommendationParamsList(EngineParamsGenerator):
    """Default tuning grid over rank/lambda (EngineParamsGenerator
    analog used by the reference's evaluation templates)."""

    def __init__(self, app_name: str = "recommendation-app"):
        super().__init__()
        self.engine_params_list = [
            EngineParams(
                data_source_params=("", DataSourceParams(app_name=app_name)),
                algorithm_params_list=[
                    ("als", ALSParams(rank=rank, num_iterations=10,
                                      lambda_=lam, seed=3))],
            )
            for rank in (8, 16)
            for lam in (0.01, 0.1)
        ]


class RecommendationEvaluation(Evaluation, RecommendationParamsList):
    """`pio eval` entry: ALS grid scored by Precision@10; best params
    land in best.json (Evaluation.scala engine_metric path).

    Also an EngineParamsGenerator (like the reference's evaluation
    templates that extend both), so ``pio eval <this-class>`` needs no
    separate generator argument and ``app_name`` reaches the
    datasource params of every grid point."""

    def __init__(self, app_name: str = "recommendation-app", k: int = 10):
        Evaluation.__init__(self)
        RecommendationParamsList.__init__(self, app_name=app_name)
        self.engine_metric = (engine_factory(), PrecisionAtK(k))


def engine_factory() -> Engine:
    """EngineFactory analog (custom-query Engine.scala:13-19). The
    custom-serving variant registers FileBlacklistServing under
    "fileblacklist" (select via engine.json serving section)."""
    return Engine(
        EventDataSource,
        RatingsPreparator,
        {"als": ALSAlgorithm, "": ALSAlgorithm},
        {"": RecommendationServing,
         "fileblacklist": FileBlacklistServing},
    )


def sharded_engine_factory() -> Engine:
    """Engine whose model stays sharded in HBM (PAlgorithm flavor) —
    deploy retrains (persistence mode 3) and serves from the device."""
    return Engine(
        EventDataSource,
        RatingsPreparator,
        {"als": ALSShardedAlgorithm, "": ALSShardedAlgorithm},
        RecommendationServing,
    )
