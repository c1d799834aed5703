"""Two-stage template: ALS retrieval + seqrec re-rank, one engine.

The first REAL multi-algorithm engine (ISSUE 20): ``EngineParams.
algorithms = [("als", ...), ("seqrec", ...)]`` trains BOTH stages from
one event stream, and :class:`~predictionio_tpu.controller.
TwoStageServing` combines them — fused into one device program on live
deployments (``workflow.create_server`` binds a
:class:`~predictionio_tpu.ops.twostage.TwoStageTopK` over both models'
tables), composed on host in the eval pipeline.

The one Preparator is the load-bearing piece: both stages MUST share
one user map and one item map (candidate positions retrieved by stage
1 index stage 2's embedding table directly in HBM), so
:class:`TwoStagePreparator` indexes the event stream once and lays it
out BOTH ways — ALX-padded rating tables for the ALS half-steps and
time-ordered bucketed sequences for the transformer — wrapped in one
:class:`TwoStagePrepared` that each algorithm unwraps its side of.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from predictionio_tpu.controller import Engine, Params, PPreparator
from predictionio_tpu.controller.controllers import TwoStageServing
from predictionio_tpu.core.context import ComputeContext
from predictionio_tpu.data.bimap import StringIndexBiMap
from predictionio_tpu.ops.als import bucket_ratings_pair
from predictionio_tpu.ops.seqrec import bucket_sequences
from predictionio_tpu.templates.recommendation.engine import (
    ALSAlgorithm,
    ALSModel,
    PreparedData,
)
from predictionio_tpu.templates.sequentialrec.engine import (
    PreparedSequences,
    SeqRecAlgorithm,
    SeqRecModel,
    SequenceDataSource,
    SequenceTrainingData,
)


@dataclasses.dataclass(frozen=True)
class TwoStagePreparatorParams(Params):
    """``max_seq_len`` caps the re-ranker's sequence buckets;
    ``max_len`` (optional) caps the ALS rating-row padding."""

    max_seq_len: int = 32
    max_len: Optional[int] = None


@dataclasses.dataclass
class TwoStagePrepared:
    """Both stages' layouts over ONE shared (user, item) index space —
    the invariant the fused candidate handoff depends on."""

    ratings: PreparedData
    sequences: PreparedSequences

    @property
    def user_map(self) -> StringIndexBiMap:
        return self.ratings.user_map

    @property
    def item_map(self) -> StringIndexBiMap:
        return self.ratings.item_map


class TwoStagePreparator(PPreparator):
    """Index the event stream ONCE, lay it out twice.

    Consumes the sequence template's :class:`SequenceTrainingData`
    (user, item, time triples). The ALS side treats each event as an
    implicit rating of 1.0 (repeat events accumulate weight through the
    normal-equations sums, the standard implicit-feedback reading); the
    sequence side time-orders each user's run and buckets it. Both
    sides carry the SAME maps object — the algorithms' models therefore
    agree bit-for-bit about every index, which
    :func:`~predictionio_tpu.ops.twostage.build_two_stage_store`
    re-checks loudly at deploy."""

    params_class = TwoStagePreparatorParams

    def prepare(self, ctx: ComputeContext,
                td: SequenceTrainingData) -> TwoStagePrepared:
        p: TwoStagePreparatorParams = self.params
        u_labels, rows = np.unique(td.users.astype(str),
                                   return_inverse=True)
        i_labels, cols = np.unique(td.items.astype(str),
                                   return_inverse=True)
        user_map = StringIndexBiMap.from_distinct(u_labels)
        item_map = StringIndexBiMap.from_distinct(i_labels)
        rows = rows.astype(np.int64)
        cols = cols.astype(np.int64)
        n_u, n_i = len(user_map), len(item_map)
        vals = np.ones(len(rows), dtype=np.float32)
        user_side, item_side = bucket_ratings_pair(
            rows, cols, vals, n_u, n_i, max_len=p.max_len)
        # time-ordered per-user runs for the sequence side, seen sets
        # for serving — one stable sort each (the source templates'
        # vectorized discipline)
        n = len(td)
        order = np.lexsort((np.arange(n), td.times, rows))
        s_rows, s_cols = rows[order], cols[order]
        starts = np.searchsorted(s_rows, np.arange(n_u))
        ends = np.searchsorted(s_rows, np.arange(n_u), side="right")
        seqs = [s_cols[starts[u]:ends[u]] for u in range(n_u)]
        seen = {u: np.unique(seqs[u]) for u in range(n_u)
                if len(seqs[u])}
        buckets = bucket_sequences(seqs, max_len=int(p.max_seq_len))
        ratings = PreparedData(user_map, item_map, user_side,
                               item_side, seen)
        sequences = PreparedSequences(user_map, item_map, buckets,
                                      seen, int(p.max_seq_len))
        return TwoStagePrepared(ratings, sequences)


class TwoStageALSAlgorithm(ALSAlgorithm):
    """Stage 1 (retrieval): the standard ALS algorithm trained on the
    shared preparation's rating side."""

    def train(self, ctx: ComputeContext,
              pd: TwoStagePrepared) -> ALSModel:
        return super().train(ctx, pd.ratings)


class TwoStageSeqRecAlgorithm(SeqRecAlgorithm):
    """Stage 2 (re-rank): the standard seqrec algorithm trained on the
    shared preparation's sequence side."""

    def train(self, ctx: ComputeContext,
              pd: TwoStagePrepared) -> SeqRecModel:
        return super().train(ctx, pd.sequences)


def engine_factory() -> Engine:
    return Engine(
        SequenceDataSource,
        TwoStagePreparator,
        {"als": TwoStageALSAlgorithm,
         "seqrec": TwoStageSeqRecAlgorithm,
         "": TwoStageALSAlgorithm},
        {"": TwoStageServing},
    )
