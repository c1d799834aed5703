"""The four templates that train ALS, each on one seeded fixture with a
user whose history is ten times the median: the factors they return are
those of the plain-numpy trainer over the same (user, item, value)
triples (``als_reference``), and the tables they hand the trainer hold
every unique pair, the long row whole."""

import numpy as np
import pytest

from als_reference import numpy_train_als, sum_duplicates
from predictionio_tpu.controller import ComputeContext
from predictionio_tpu.ops.als import ALSParams
from predictionio_tpu.parallel import als_sharding

CTX = ComputeContext()
N_USERS, N_ITEMS, LONG_USER, LONG_LEN = 24, 120, 5, 90
HYPER = dict(rank=6, num_iterations=3, lambda_=0.1, seed=4)


def events():
    """(user label, item label, value) in arrival order: every user has
    5 to 11 events (some pairs twice), user 5 has 90 distinct items."""
    rng = np.random.default_rng(12)
    out = []
    for u in range(N_USERS):
        picks = rng.choice(N_ITEMS, LONG_LEN, replace=False) \
            if u == LONG_USER \
            else rng.integers(0, N_ITEMS, rng.integers(5, 12))
        out += [(f"u{u}", f"i{i}", float(rng.integers(1, 6))) for i in picks]
    order = rng.permutation(len(out))
    return [out[k] for k in order]


def _recommendation(evs):
    from predictionio_tpu.templates.recommendation.engine import (
        ALSAlgorithm, PreparatorParams, RatingsPreparator, TrainingData)

    u, i, v = zip(*evs)
    pd = RatingsPreparator(PreparatorParams()).prepare(CTX, TrainingData(
        users=np.asarray(u, dtype=object), items=np.asarray(i, dtype=object),
        values=np.asarray(v, dtype=np.float32)))
    m = ALSAlgorithm(ALSParams(**HYPER)).train(CTX, pd)
    return m.user_map, m.item_map, v, m.user_factors, m.item_factors


def _twostage(evs):
    from predictionio_tpu.templates.sequentialrec.engine import (
        SequenceTrainingData)
    from predictionio_tpu.templates.twostage.engine import (
        TwoStageALSAlgorithm, TwoStagePreparator, TwoStagePreparatorParams)

    u, i, _ = zip(*evs)
    pd = TwoStagePreparator(TwoStagePreparatorParams(max_seq_len=16)).prepare(
        CTX, SequenceTrainingData(
            np.asarray(u, dtype=object), np.asarray(i, dtype=object),
            np.arange(len(evs), dtype=np.float64)))
    m = TwoStageALSAlgorithm(ALSParams(**HYPER)).train(CTX, pd)
    # each view is an implicit rating of 1.0; repeats add up
    return m.user_map, m.item_map, [1.0] * len(evs), \
        m.user_factors, m.item_factors


def _labels():
    """The maps ``BiMap.string_int`` builds from the ``$set`` dicts."""
    users = {f"u{u}": None for u in range(N_USERS)}
    items = [f"i{i}" for i in range(N_ITEMS)]
    return users, items, {k: n for n, k in enumerate(users)}, \
        {k: n for n, k in enumerate(items)}


def _similarproduct(evs):
    from predictionio_tpu.templates.similarproduct.engine import (
        ALSAlgorithm, ALSAlgorithmParams, Item, TrainingData, ViewEvent)

    users, items, user_map, item_map = _labels()
    m = ALSAlgorithm(ALSAlgorithmParams(**HYPER)).train(CTX, TrainingData(
        users, {k: Item() for k in items},
        [ViewEvent(u, i) for u, i, _ in evs]))
    return user_map, item_map, [1.0] * len(evs), None, m.product_features


def _ecommerce(evs):
    from predictionio_tpu.templates.ecommercerecommendation.engine import (
        ECommAlgorithm, ECommAlgorithmParams, Item, RateEvent, TrainingData)

    users, items, user_map, item_map = _labels()
    m = ECommAlgorithm(ECommAlgorithmParams(app_name="x", **HYPER)).train(
        CTX, TrainingData(users, {k: Item() for k in items},
                          [RateEvent(u, i, v) for u, i, v in evs]))
    return user_map, item_map, [v for _, _, v in evs], \
        m.user_features, m.product_features


TEMPLATES = pytest.mark.parametrize("train", [
    _recommendation, _similarproduct, _ecommerce, _twostage],
    ids=lambda f: f.__name__.lstrip("_"))


def triples(evs, user_map, item_map, vals):
    return ([user_map[u] for u, _, _ in evs],
            [item_map[i] for _, i, _ in evs], vals)


@TEMPLATES
def test_factors_match_numpy_trainer(train):
    evs = events()
    user_map, item_map, vals, X, Y = train(evs)
    # (the maps of the two templates that index what they read hold
    # only the items some event names)
    Xn, Yn = numpy_train_als(*triples(evs, user_map, item_map, vals),
                             len(user_map), len(item_map),
                             ALSParams(**HYPER))
    # float32 einsums against float64 per-row solves, three iterations;
    # a dropped or misplaced pair moves a factor by O(0.1)
    np.testing.assert_allclose(Y, Yn, rtol=2e-3, atol=2e-4)
    if X is not None:
        np.testing.assert_allclose(X, Xn, rtol=2e-3, atol=2e-4)


@TEMPLATES
def test_long_row_loses_no_pair(train, monkeypatch):
    handed = []
    real = als_sharding.train_als_auto

    def recorded(user_side, item_side, params, **kw):
        handed.append((user_side, item_side))
        return real(user_side, item_side, params, **kw)

    from predictionio_tpu.templates.ecommercerecommendation import (
        engine as ecommerce)
    from predictionio_tpu.templates.similarproduct import (
        engine as similarproduct)

    monkeypatch.setattr(als_sharding, "train_als_auto", recorded)
    monkeypatch.setattr(ecommerce, "_train_als_auto", recorded)
    monkeypatch.setattr(similarproduct, "_train_als_auto", recorded)
    evs = events()
    user_map, item_map, vals, _, _ = train(evs)
    (user_side, item_side), = handed
    rows, cols, summed = sum_duplicates(
        *triples(evs, user_map, item_map, vals))
    counts = np.bincount(rows, minlength=N_USERS)
    long_row = user_map[f"u{LONG_USER}"]
    assert counts[long_row] == LONG_LEN >= 10 * np.median(counts)
    assert user_side.nnz == item_side.nnz == len(rows)
    # the long row, whole and with its values, in the top bucket
    top = max(user_side.buckets, key=lambda b: b.max_len)
    (at,), = np.nonzero(np.asarray(top.row_ids) == long_row)
    live = top.mask[at] > 0
    sel = rows == long_row
    assert dict(zip(top.cols[at][live].tolist(),
                    top.weights[at][live].tolist())) == \
        dict(zip(cols[sel].tolist(), summed[sel].tolist()))


def test_bucketed_field_changes_nothing():
    """``PreparatorParams.bucketed`` is accepted (the benchmark passes
    it) and no longer read: both values prepare the same tables."""
    from predictionio_tpu.templates.recommendation.engine import (
        PreparatorParams, RatingsPreparator, TrainingData)

    u, i, v = zip(*events())
    td = TrainingData(
        users=np.asarray(u, dtype=object), items=np.asarray(i, dtype=object),
        values=np.asarray(v, dtype=np.float32))
    off, on = (RatingsPreparator(PreparatorParams(bucketed=flag, max_len=40))
               .prepare(CTX, td) for flag in (False, True))
    for a, b in ((off.user_side, on.user_side),
                 (off.item_side, on.item_side)):
        assert (a.n_rows, a.n_cols) == (b.n_rows, b.n_cols)
        assert len(a.buckets) == len(b.buckets)
        for x, y in zip(a.buckets, b.buckets):
            for f in ("row_ids", "cols", "weights", "mask"):
                np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
    # max_len reaches the tables either way: the 90-item row is cut
    assert [b.max_len for b in off.user_side.buckets] == [16, 40]
