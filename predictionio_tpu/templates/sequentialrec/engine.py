"""Sequential-recommendation engine: per-user event sequences ->
SASRec-style next-item prediction (ROADMAP item 1 — the first workload
on the ring/Ulysses attention kernels; the reference framework has no
sequence-model family at all, PARITY §2.6).

DASE shape mirrors ``templates/recommendation`` so the whole serving
plane is inherited, not rebuilt:

- DataSource reads time-stamped interaction events (``view`` by
  default) via the columnar bulk path — optionally streamed in bounded
  blocks through the PR-6 ``find_columnar_blocks`` with a decode
  prefetch hint — and evaluates with the SAME sliding-window /
  leave-last-out protocols (one shared split helper,
  ``data/sliding.py``).
- The Preparator indexes users/items with BiMaps, orders each user's
  items by event time and groups them into power-of-two length buckets
  (``ops/seqrec.bucket_sequences`` — the ``ops/als.PAD_MULTIPLE``
  discipline, one compiled program per length class).
- ``SeqRecAlgorithm`` trains the causal transformer encoder
  (``ops/seqrec.train_seqrec``: ``lax.scan`` over Adam steps, sampled
  softmax over the item vocabulary) and encodes every user's sequence
  into a vector; the model is served EXACTLY like an ALS model — user
  vectors × the (tied) item embedding table through
  ``choose_server``/``DeviceTopK`` — so continuous batching, the AOT
  bucket ladder, bf16/int8 serving precision, device telemetry and
  crash-safe deploys all apply with zero new serving code.
- Online fold-in: the model exposes ``fold_in_rows`` (re-encode the
  touched users' full time-ordered sequences on device), so ``pio
  deploy --foldin on`` patches fresh user vectors into the live store
  on new events — no retrain, no ``/reload``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.controller import (
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    LFirstServing,
    P2LAlgorithm,
    Params,
    PDataSource,
    PPreparator,
)
from predictionio_tpu.core.context import ComputeContext
from predictionio_tpu.data.bimap import StringIndexBiMap
from predictionio_tpu.data.sliding import (
    group_by_entity,
    leave_last_out,
    sliding_window_masks,
)
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.seqrec import (
    PackedRows,
    SeqRecParams,
    SequenceBucket,
    bucket_sequences,
    encode_users,
    length_bucket,
    output_table,
    pack_sequences,
    train_seqrec,
)

# the serving-side types and plumbing are the recommendation
# template's — ONE definition of the query/result surface and of the
# device-serving glue, so this template inherits every serving-plane
# improvement automatically
from predictionio_tpu.templates.recommendation.engine import (
    ActualResult,
    EmptyEvalInfo,
    ItemScore,
    PredictedResult,
    PrecisionAtK,
    Query,
    _coerce_query,
    _DeviceServedModel,
    _DeviceServingAlgo,
    _winners_to_result,
)


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    """``streaming_block_size`` streams the read through the PR-6
    ``find_columnar_blocks`` (bounded blocks in storage order,
    ``decode_prefetch`` partitions decoded ahead); the sliding-window
    eval knobs are the recommendation template's
    (EventsSlidingEvalParams semantics, shared split helper)."""

    app_name: str
    event_names: Tuple[str, ...] = ("view",)
    channel_name: Optional[str] = None
    streaming_block_size: Optional[int] = None
    decode_prefetch: int = 0
    # sliding-window evaluation (shared protocol + helper with
    # templates/recommendation): eval_count = 0 keeps leave-last-out
    eval_first_until: Optional[str] = None   # ISO-8601
    eval_duration_days: float = 7.0
    eval_count: int = 0


# blocks served from per-user caches (``ops/sessions.py``)
SESSION_BLOCKS = ("glm_moe_dsa", "sdar_moe", "smallthinker", "qwen3_next",
                  "falcon_h1")


class SequenceTrainingData:
    """Columnar (user, item, time) interaction triples in storage order
    — the Preparator does the time sort once, vectorized."""

    def __init__(self, users: np.ndarray, items: np.ndarray,
                 times: np.ndarray):
        self.users = users
        self.items = items
        self.times = times
        if not (len(users) == len(items) == len(times)):
            raise ValueError(
                f"misaligned sequence columns: {len(users)} users, "
                f"{len(items)} items, {len(times)} times")

    def __len__(self) -> int:
        return int(self.users.shape[0])

    def sanity_check(self) -> None:
        assert len(self), (
            "events in SequenceTrainingData cannot be empty. Please "
            "check if DataSource generates TrainingData correctly.")


class SequenceDataSource(PDataSource):
    """Time-stamped interaction events -> columnar sequence triples."""

    params_class = DataSourceParams

    def _read_columns(self, until_time=None) -> SequenceTrainingData:
        p: DataSourceParams = self.params
        kwargs = dict(
            app_name=p.app_name, channel_name=p.channel_name,
            entity_type="user", event_names=list(p.event_names),
            target_entity_type="item", value_property=None,
            default_value=1.0, until_time=until_time)
        if p.streaming_block_size:
            users_l, items_l, times_l = [], [], []
            for block in PEventStore.find_columnar_blocks(
                    block_size=int(p.streaming_block_size),
                    prefetch=int(p.decode_prefetch), **kwargs):
                block = block.materialize()
                users_l.append(block.entity_ids)
                items_l.append(block.target_ids)
                times_l.append(block.event_times)
            if users_l:
                users = np.concatenate(users_l)
                items = np.concatenate(items_l)
                times = np.concatenate(times_l)
            else:
                users = np.empty(0, dtype=object)
                items = np.empty(0, dtype=object)
                times = np.empty(0, dtype=np.float64)
        else:
            batch = PEventStore.find_columnar(**kwargs)
            users, items, times = (batch.entity_ids, batch.target_ids,
                                   batch.event_times)
        # events without a target id cannot join a sequence
        keep = np.fromiter((x is not None for x in items), dtype=bool,
                           count=len(items))
        if not keep.all():
            users, items, times = users[keep], items[keep], times[keep]
        return SequenceTrainingData(users, items, times)

    def read_training(self, ctx: ComputeContext) -> SequenceTrainingData:
        return self._read_columns()

    def read_eval(self, ctx: ComputeContext):
        p: DataSourceParams = self.params
        if p.eval_count > 0:
            return self._sliding_eval(p)
        td = self._read_columns()
        # leave-last-out in TIME order per user (shared helper): the
        # held-out event is each user's most recent item
        n = len(td)
        users_str = td.users.astype(str)
        order = np.lexsort((np.arange(n), td.times, users_str))
        groups = group_by_entity(users_str[order], list(order))
        train_idx, held = leave_last_out(groups)
        train_idx = np.asarray(sorted(train_idx), dtype=np.int64)
        train = SequenceTrainingData(td.users[train_idx],
                                     td.items[train_idx],
                                     td.times[train_idx])
        qa = [(Query(user=u, num=10),
               ActualResult([str(td.items[i])])) for u, i in held]
        return [(train, EmptyEvalInfo(), qa)]

    def _sliding_eval(self, p: DataSourceParams):
        """Sliding time windows — the recommendation template's
        protocol, split math in ``data/sliding.py``."""
        import datetime as _dt

        from predictionio_tpu.data.event import _parse_time

        if not p.eval_first_until:
            raise ValueError(
                "eval_count > 0 requires eval_first_until (ISO-8601)")
        first_until = _parse_time(p.eval_first_until)
        t0 = first_until.timestamp()
        dur = float(p.eval_duration_days) * 86400.0
        horizon = first_until + _dt.timedelta(
            seconds=dur * int(p.eval_count))
        td = self._read_columns(until_time=horizon)
        sets = []
        for k, train_mask, test_mask in sliding_window_masks(
                td.times, t0, dur, int(p.eval_count),
                hint="move eval_first_until later or reduce eval_count"):
            train = SequenceTrainingData(td.users[train_mask],
                                         td.items[train_mask],
                                         td.times[train_mask])
            held: Dict[str, List[str]] = {}
            for u, i in zip(td.users[test_mask], td.items[test_mask]):
                held.setdefault(str(u), []).append(str(i))
            qa = [(Query(user=u, num=10), ActualResult(items))
                  for u, items in held.items()]
            sets.append((train, EmptyEvalInfo(), qa))
        return sets


@dataclasses.dataclass(frozen=True)
class SeqPreparatorParams(Params):
    """``max_seq_len`` keeps each user's LAST that-many items (recency
    is the signal); the padded length classes round it up the
    power-of-two ladder. ``packed`` lays the histories out first-fit in
    rows of ``max_seq_len`` slots with segment ids instead
    (``ops/seqrec.pack_sequences``): the layout of a long-row backbone,
    where padding a mean-59 history to a power of two would waste most
    of a row."""

    max_seq_len: int = 32
    packed: bool = False


@dataclasses.dataclass
class PreparedSequences:
    """BiMap-indexed per-user sequences in the layout the trainer
    reads: length buckets, or one :class:`PackedRows`."""

    user_map: StringIndexBiMap
    item_map: StringIndexBiMap
    buckets: Any   # List[SequenceBucket] | PackedRows
    seen: Dict[int, np.ndarray]   # user idx -> unique item idx array
    max_seq_len: int
    # user idx -> time-ordered item idx (the last max_seq_len): what a
    # session-served model keeps to build its per-user caches from
    sequences: Optional[List[np.ndarray]] = None

    def sanity_check(self) -> None:
        assert len(self.user_map) > 0, "no users after indexing"
        assert len(self.item_map) > 0, "no items after indexing"
        assert len(self.buckets), "no non-empty sequences after bucketing"


class SequencePreparator(PPreparator):
    """Index -> time-order -> bucket. One vectorized sort: rows are
    ordered by (user, event time, arrival) and split into per-user
    runs; each run is that user's sequence."""

    params_class = SeqPreparatorParams

    def prepare(self, ctx: ComputeContext,
                td: SequenceTrainingData) -> PreparedSequences:
        p: SeqPreparatorParams = self.params
        users_str = td.users.astype(str)
        items_str = td.items.astype(str)
        u_labels, rows = np.unique(users_str, return_inverse=True)
        i_labels, cols = np.unique(items_str, return_inverse=True)
        user_map = StringIndexBiMap.from_distinct(u_labels)
        item_map = StringIndexBiMap.from_distinct(i_labels)
        n = len(td)
        order = np.lexsort((np.arange(n), td.times, rows))
        s_rows = rows[order]
        s_cols = cols[order].astype(np.int64)
        n_u = len(user_map)
        starts = np.searchsorted(s_rows, np.arange(n_u))
        ends = np.searchsorted(s_rows, np.arange(n_u), side="right")
        seqs = [s_cols[starts[u]:ends[u]] for u in range(n_u)]
        seen = {u: np.unique(seqs[u]) for u in range(n_u) if len(seqs[u])}
        return PreparedSequences(user_map, item_map,
                                 self.layout(seqs), seen,
                                 int(p.max_seq_len),
                                 [q[-int(p.max_seq_len):] for q in seqs])

    def layout(self, seqs):
        """Per-user index sequences -> the trainer's layout."""
        from predictionio_tpu.utils import metrics, tracing

        p: SeqPreparatorParams = self.params
        if not p.packed:
            return bucket_sequences(seqs, max_len=int(p.max_seq_len))
        with tracing.span("seq.pack"):
            rows = pack_sequences(seqs, int(p.max_seq_len))
        metrics.SEQ_PACK_PAD_SHARE.set(rows.pad_share)
        return rows


@dataclasses.dataclass
class SeqRecModel(_DeviceServedModel):
    """User vectors + the OUTPUT table (the tied item embedding table
    of a SASRec block, the separate ``out_emb`` of an untied backbone),
    served through the standard factor-store top-k path
    (``choose_server`` -> ``DeviceTopK`` on device backends) exactly
    like an ALS model — plus the encoder parameters, so fold-in can
    RE-ENCODE a user's sequence instead of re-solving a linear
    system.

    A ``glm_moe_dsa``, ``sdar_moe``, ``smallthinker``, ``qwen3_next`` or
    ``falcon_h1`` block is SESSION-served instead
    (``ops/sessions.py::SessionTopK``): a backbone that wide has no
    user-vector table worth holding, so each user's history lives on
    the device as a cache (built at deploy from ``histories``) and a
    query ``{"user": u, "items": [new events], "num": n}`` extends it
    (``sdar_moe``: and generates a SLATE of ``n`` items by diffusion
    over blocks: ``itemScores`` in slate order, each score the item's
    confidence).
    With ``seededWeights`` (and ``numSteps: 0``) such a model carries
    an EMPTY ``theta`` and no vectors: the backbone's seeded initial
    weights are drawn on the device at deploy from
    ``enc_params.seed``."""

    user_vectors: Optional[np.ndarray]      # [N, R]
    item_vectors: Optional[np.ndarray]      # [M, R]: output_table(theta)[:M]
    user_map: StringIndexBiMap
    item_map: StringIndexBiMap
    seen: Dict[int, np.ndarray]
    theta: Dict[str, np.ndarray]
    enc_params: SeqRecParams
    max_seq_len: int
    histories: Optional[Dict[int, np.ndarray]] = None
    _server: Any = dataclasses.field(default=None, repr=False,
                                     compare=False)

    # online fold-in (online/foldin.py): gather this model's touched
    # users' histories in EVENT-TIME order — re-encoding is order-
    # sensitive, unlike the ALS normal-equations solve
    foldin_time_ordered = True
    # transformer logits are only relatively calibrated: a user whose
    # unseen-item dot products are ALL negative still has a valid
    # ranking, so serving must not drop negative finite scores (the
    # implicit-ALS positivity filter would truncate their results)
    serve_positive_scores_only = False

    @property
    def session_served(self) -> bool:
        return self.enc_params.block in SESSION_BLOCKS

    # the fold-in consumer hands such a model the touched users' ids,
    # so that a fold APPENDS to their sessions
    foldin_wants_ids = property(lambda self: self.session_served)

    def _make_server(self):
        from predictionio_tpu.ops.serving import choose_server

        if not self.session_served:
            return choose_server(self.user_vectors, self.item_vectors,
                                 self.seen)
        from predictionio_tpu.ops.seqrec import table_rows
        from predictionio_tpu.ops.sessions import SessionTopK, backbone_of

        p = self.enc_params
        n_items = len(self.item_map)
        backbone = backbone_of(p)
        if self.theta:
            theta = backbone.serving_theta(self.theta)
        elif p.seeded_weights:
            theta = backbone.draw_theta(table_rows(n_items, p), p)
        else:
            raise ValueError(f"a {p.block} model without weights is "
                             "served only with seededWeights")
        return SessionTopK(theta["out_emb"][:n_items], theta, p,
                           n_users=len(self.user_map),
                           histories=self.histories, seen=self.seen,
                           pool_tokens=int(p.session_pool_tokens),
                           audit=int(p.session_audit), backbone=backbone)

    def _device_theta(self):
        """Encoder params as DEVICE arrays, cached: the host-numpy
        theta would otherwise re-transfer the whole model (item table
        included) H2D on EVERY fold at the ~2s cadence. Dropped at
        pickle like the serving handles."""
        th = getattr(self, "_theta_device", None)
        if th is None:
            import jax.numpy as jnp

            th = {k: jnp.asarray(v) for k, v in self.theta.items()}
            self._theta_device = th
        return th

    def fold_in_rows(self, cols_list, vals_list, ids=None) -> np.ndarray:
        """Re-encode ``k`` users' full time-ordered item sequences into
        fresh ``[k, R]`` user vectors — the fold-in consumer's solve
        hook (the sequence-model analog of ``ops.als.fold_in_users``).
        The batch pads to power-of-two (rows, length) classes so a
        long-lived server's folds reuse a handful of compiled encode
        programs. A session-served model APPENDS instead (``ids``: the
        users' entity ids): the events past what a user's session
        already caches go through the session lane's program."""
        from predictionio_tpu.ops.serving import bucket_size

        if self.session_served:
            return self._fold_in_sessions(cols_list, ids)
        k = len(cols_list)
        if k == 0:
            return np.zeros((0, self.item_vectors.shape[1]),
                            dtype=np.float32)
        seqs = []
        for c in cols_list:
            c = np.asarray(c, dtype=np.int32)
            if len(c) > self.max_seq_len:
                c = c[-self.max_seq_len:]
            seqs.append(c)
        longest = max((len(s) for s in seqs), default=1)
        L = length_bucket(max(longest, 1))
        B = bucket_size(k, 8)
        ids = np.zeros((B, L), dtype=np.int32)
        mask = np.zeros((B, L), dtype=np.float32)
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
            mask[i, :len(s)] = 1.0
        from predictionio_tpu.ops.seqrec import encode_bucket

        bucket = SequenceBucket(np.arange(B, dtype=np.int64), ids, mask)
        return encode_bucket(self._device_theta(), bucket,
                             self.enc_params)[:k]

    def _fold_in_sessions(self, cols_list, ids) -> np.ndarray:
        """Append each touched user's new events to their session and
        return the sessions' last hidden states ``[k, R]`` (what
        ``patch_users`` then writes is what the lane already holds).
        An application sends a user's new events EITHER with its
        queries or to the event store for this fold to find: one that
        does both appends them twice. A user the model does not know
        yet has no row to hold a session under: their history is
        encoded into blocks that are given back at once
        (``SessionTopK.encode``) and waits in ``_fold_histories``
        until their first query opens their session."""
        srv = self.device_server()
        if ids is None:
            raise ValueError("a session-served model folds by user id")
        pending = self.__dict__.setdefault("_fold_histories", {})
        folded = self.__dict__.setdefault("_folded", {})
        rows = []
        for uid, cols in zip(ids, cols_list):
            cols = np.asarray(cols, dtype=np.int32)
            uidx = self.user_map.get(uid)
            if uidx is None:
                pending[uid] = cols
                rows.append(srv.encode(cols))
                continue
            # the STORE's events past those already folded (the
            # model's history at train time, then every fold's);
            # events a query brought itself are not in the store and
            # are not counted
            have = folded.get(uidx, len((self.histories or {}).get(
                uidx, ())))
            srv.sess_topk(uidx, cols[have:], 1)
            folded[uidx] = max(have, len(cols))
            rows.append(srv.last_hidden(uidx))
        return np.stack(rows) if rows else np.zeros(
            (0, int(self.enc_params.rank)), np.float32)

    def sanity_check(self) -> None:
        for name in ("user_vectors", "item_vectors"):
            a = getattr(self, name)
            assert a is None or np.isfinite(a).all(), f"non-finite {name}"


class SeqRecAlgorithm(_DeviceServingAlgo, P2LAlgorithm):
    """SASRec-style next-item transformer on the attention kernels."""

    params_class = SeqRecParams
    query_cls = Query

    def train(self, ctx: ComputeContext,
              pd: PreparedSequences) -> SeqRecModel:
        import jax

        from predictionio_tpu.utils import tracing

        p = dataclasses.replace(self.params,
                                max_seq_len=pd.max_seq_len) \
            if self.params.max_seq_len != pd.max_seq_len else self.params
        # one local root per call (a child span inside `pio train`'s
        # root): stage / steps / encode_users / fetch. The parameters
        # stay on the device from the first step to the last encode
        # call; one transfer at the end brings model and vectors down.
        histories = None
        if p.block in SESSION_BLOCKS:
            histories = {u: np.asarray(q, dtype=np.int32)
                         for u, q in enumerate(pd.sequences) if len(q)}
            if int(p.num_steps) == 0:
                if not p.seeded_weights:
                    raise ValueError(
                        f"numSteps 0 trains nothing: a {p.block} model "
                        "is persisted without weights only with "
                        "seededWeights (its seeded initial weights are "
                        "then drawn at deploy)")
                self.last_losses = []
                return SeqRecModel(None, None, pd.user_map, pd.item_map,
                                   pd.seen, {}, p, pd.max_seq_len,
                                   histories)
        with tracing.trace_scope("seq.train", slow_exempt=True):
            theta, losses = train_seqrec(pd.buckets, len(pd.item_map), p,
                                         to_host=False)
            # a mesh means the sequence-parallel kernels encode (ring /
            # Ulysses selected per length class; the same topology
            # policy as train_als_auto's single-host branch)
            mesh = None
            if len(jax.devices()) > 1 and p.sp_mode != "off" \
                    and not isinstance(pd.buckets, PackedRows):
                from predictionio_tpu.parallel.mesh import (
                    data_parallel_mesh,
                )

                mesh = data_parallel_mesh()
            with tracing.span("seq.encode_users"):
                U = encode_users(theta, pd.buckets, len(pd.user_map), p,
                                 mesh=mesh, to_host=False)
                jax.block_until_ready(U)
            with tracing.span("seq.fetch"):
                theta, U = jax.device_get((theta, U))
        self.last_losses = losses
        return SeqRecModel(U, output_table(theta)[:len(pd.item_map)],
                           pd.user_map, pd.item_map, pd.seen, theta, p,
                           pd.max_seq_len, histories)

    def predict(self, model: SeqRecModel, query) -> PredictedResult:
        """A session-served model takes the upstream query form with
        BOTH fields, ``{"user": u, "items": [new events, oldest
        first], "num": n}``: append the events to ``u``'s session and
        recommend. Every other model, and an item-only query, is
        served as the recommendation template serves it."""
        query = _coerce_query(query)
        if not model.session_served or query.user is None:
            return super().predict(model, query)
        item_map = model.item_map
        uidx = model.user_map.get(query.user)
        if uidx is None:
            return PredictedResult(())
        srv = model.device_server()
        waiting = model.__dict__.get("_fold_histories", {}).pop(
            query.user, None)
        if waiting is not None:
            srv.open_session(uidx, waiting)
        events = [item_map[i] for i in query.items if i in item_map]
        black = {item_map[i] for i in query.blacklist if i in item_map}
        idx, scores = srv.sess_topk(uidx, events, query.num + len(black))
        # (a slate comes back in position order, a top-k by score: the
        # result keeps the order it is given)
        return _winners_to_result(idx, scores, black, query.num, item_map,
                                  positive_only=False)

    def batch_predict(self, ctx: ComputeContext, model: SeqRecModel,
                      indexed_queries) -> List[Tuple[int, Any]]:
        return self._batched_predict(model, indexed_queries)


class SeqRecServing(LFirstServing):
    """First-serving, like the recommendation template."""


class SeqRecParamsList(EngineParamsGenerator):
    """Small tuning grid over width/depth."""

    def __init__(self, app_name: str = "seqrec-app"):
        super().__init__()
        self.engine_params_list = [
            EngineParams(
                data_source_params=("", DataSourceParams(
                    app_name=app_name)),
                preparator_params=("", SeqPreparatorParams()),
                algorithm_params_list=[
                    ("seqrec", SeqRecParams(rank=rank, n_layers=layers,
                                            seed=7))],
            )
            for rank in (16, 32)
            for layers in (1, 2)
        ]


class SeqRecEvaluation(Evaluation, SeqRecParamsList):
    """``pio eval`` entry: the width/depth grid scored by Precision@10
    over the leave-last-out (or sliding-window) split."""

    def __init__(self, app_name: str = "seqrec-app", k: int = 10):
        Evaluation.__init__(self)
        SeqRecParamsList.__init__(self, app_name=app_name)
        self.engine_metric = (engine_factory(), PrecisionAtK(k))


def engine_factory() -> Engine:
    return Engine(
        SequenceDataSource,
        SequencePreparator,
        {"seqrec": SeqRecAlgorithm, "": SeqRecAlgorithm},
        SeqRecServing,
    )
