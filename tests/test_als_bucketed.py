"""Length-bucketed ALS: numerics held to the plain-numpy trainer over
the triples, few padded slots on power-law data, nothing truncated by
default (100% unique-pair coverage — MLlib's full-RDD semantics,
custom-query ALSAlgorithm.scala:64-71)."""

import numpy as np
import pytest

from als_reference import numpy_train_als
from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings,
    dedup_sum_ratings,
    train_als_bucketed,
)


def powerlaw_triples(n_users=220, n_items=90, nnz=4000, seed=3):
    rng = np.random.default_rng(seed)
    up = 1.0 / np.arange(1, n_users + 1) ** 0.9
    ip = 1.0 / np.arange(1, n_items + 1) ** 0.9
    rows = rng.choice(n_users, size=nnz, p=up / up.sum())
    cols = rng.choice(n_items, size=nnz, p=ip / ip.sum())
    vals = rng.integers(1, 6, size=nnz).astype(np.float32)
    return rows, cols, vals


class TestBucketConstruction:
    def test_covers_every_unique_pair(self):
        rows, cols, vals = powerlaw_triples()
        b = bucket_ratings(rows, cols, vals, 220, 90)
        ur, uc, uv = dedup_sum_ratings(rows, cols, vals, 90)
        assert b.nnz == len(ur)  # nothing truncated
        # every entry present exactly once, values summed
        got = {}
        for bk in b.buckets:
            real = bk.row_ids < 220
            for i in np.nonzero(real)[0]:
                r = int(bk.row_ids[i])
                m = bk.mask[i] > 0
                for c, v in zip(bk.cols[i][m], bk.weights[i][m]):
                    got[(r, int(c))] = float(v)
        want = {(int(r), int(c)): float(v) for r, c, v in zip(ur, uc, uv)}
        assert got == want

    def test_nnz_counts_exactly_past_float32_integers(self):
        """The masks are float32; the pair count must not be a float32
        running total, which stops counting exactly at 2^24 (the
        ML-20M shape's 17.5M pairs came out one short)."""
        from predictionio_tpu.ops.als import BucketedRatings, RatingsBucket

        def bucket(mask):
            z = np.zeros((1, 1))
            return RatingsBucket(np.arange(len(mask), dtype=np.int32),
                                 z.astype(np.int32), z.astype(np.float32),
                                 mask)

        big = np.ones((4096, 4100), dtype=np.float32)  # 16,793,600 > 2^24
        side = BucketedRatings(
            [bucket(big), bucket(np.ones((1, 3), dtype=np.float32))],
            4096, 10)
        assert side.nnz == 4096 * 4100 + 3

    def test_occupancy_beats_longest_row_padding(self):
        rows, cols, vals = powerlaw_triples(n_users=800, n_items=600,
                                            nnz=8000)
        b = bucket_ratings(rows, cols, vals, 800, 600)
        counts = np.bincount(dedup_sum_ratings(rows, cols, vals, 600)[0],
                             minlength=800)
        # one table padded to the longest row, counted by hand
        longest_row_slots = 800 * (-(-int(counts.max()) // 8) * 8)
        assert b.padded_slots < longest_row_slots / 3
        assert b.occupancy > 0.3
        # each bucket's slots, counted by hand: its rows (to a multiple
        # of 8) times its own length
        lengths = np.asarray(sorted(bk.max_len for bk in b.buckets))
        of_row = lengths[np.searchsorted(lengths, counts[counts > 0])]
        want = sum(-(-int((of_row == L).sum()) // 8) * 8 * int(L)
                   for L in lengths)
        assert b.padded_slots == want

    def test_each_row_in_smallest_fitting_bucket(self):
        rows, cols, vals = powerlaw_triples()
        b = bucket_ratings(rows, cols, vals, 220, 90,
                           bucket_lengths=(8, 16, 64))
        counts = np.bincount(dedup_sum_ratings(rows, cols, vals, 90)[0],
                             minlength=220)
        ls = sorted(bk.max_len for bk in b.buckets)
        for bk in b.buckets:
            smaller = [x for x in ls if x < bk.max_len]
            lo = smaller[-1] if smaller else 0
            real = bk.row_ids[bk.row_ids < 220]
            assert np.all(counts[real] <= bk.max_len)
            assert np.all(counts[real] > lo)

    def test_max_len_truncates_keeping_strongest(self):
        rows = np.zeros(10, dtype=np.int64)
        cols = np.arange(10, dtype=np.int64)
        vals = np.arange(1, 11, dtype=np.float32)
        b = bucket_ratings(rows, cols, vals, 4, 10, max_len=4,
                           pad_multiple=1, row_multiple=1)
        assert b.nnz == 4
        kept = sorted(
            float(v) for bk in b.buckets
            for v in bk.weights[bk.mask > 0])
        assert kept == [7.0, 8.0, 9.0, 10.0]

    def test_empty_rows_excluded(self):
        b = bucket_ratings(np.asarray([0, 5]), np.asarray([1, 2]),
                           np.asarray([1.0, 2.0]), 50, 10)
        real = np.concatenate(
            [bk.row_ids[bk.row_ids < 50] for bk in b.buckets])
        assert sorted(real.tolist()) == [0, 5]


class TestBucketedTraining:
    @pytest.mark.parametrize("implicit,dislikes", [
        (True, False), (False, False), (True, True)])
    def test_matches_numpy_trainer(self, implicit, dislikes):
        rows, cols, vals = powerlaw_triples()
        if dislikes:   # MLlib trainImplicit: confidence |r|, pref r > 0
            vals = np.where(vals == 1.0, -3.0, vals)
        params = ALSParams(rank=8, num_iterations=3, lambda_=0.05,
                           alpha=1.0, implicit_prefs=implicit, seed=4)
        Xn, Yn = numpy_train_als(rows, cols, vals, 220, 90, params)
        Xb, Yb = train_als_bucketed(
            bucket_ratings(rows, cols, vals, 220, 90),
            bucket_ratings(cols, rows, vals, 90, 220), params)
        # float32 einsums against float64 per-row solves; the explicit
        # lane (ALS-WR lambda*n scaling, larger dynamic range) is the
        # looser one. A layout bug diverges by O(1).
        np.testing.assert_allclose(Xb, Xn, rtol=5e-3, atol=2e-5)
        np.testing.assert_allclose(Yb, Yn, rtol=5e-3, atol=2e-5)

    def test_slot_budget_blocked_solves_match(self):
        rows, cols, vals = powerlaw_triples(nnz=3000)
        params = ALSParams(rank=8, num_iterations=2, seed=1)
        free = train_als_bucketed(
            bucket_ratings(rows, cols, vals, 220, 90),
            bucket_ratings(cols, rows, vals, 90, 220), params)
        budgeted = train_als_bucketed(
            bucket_ratings(rows, cols, vals, 220, 90),
            bucket_ratings(cols, rows, vals, 90, 220),
            ALSParams(rank=8, num_iterations=2, seed=1,
                      bucket_slot_budget=1024))
        np.testing.assert_allclose(budgeted[0], free[0], rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(budgeted[1], free[1], rtol=2e-4,
                                   atol=2e-5)

    def test_device_staged_tables_train(self):
        rows, cols, vals = powerlaw_triples(nnz=1500)
        us = bucket_ratings(rows, cols, vals, 220, 90).to_device()
        its = bucket_ratings(cols, rows, vals, 90, 220).to_device()
        X, Y = train_als_bucketed(us, its,
                                  ALSParams(rank=6, num_iterations=2,
                                            seed=0))
        assert X.shape == (220, 6) and Y.shape == (90, 6)
        assert np.isfinite(X).all() and np.isfinite(Y).all()

    def test_duplicates_summed(self):
        # reduceByKey(_ + _) parity (custom-query ALSAlgorithm.scala:50)
        rows = np.asarray([0, 0, 1, 1, 1])
        cols = np.asarray([2, 2, 0, 0, 1])
        vals = np.asarray([1.0, 2.0, 3.0, 1.0, 5.0], dtype=np.float32)
        side = bucket_ratings(rows, cols, vals, 2, 3)
        [bk] = side.buckets
        assert bk.weights[0][bk.mask[0] > 0].tolist() == [3.0]
        assert bk.weights[1][bk.mask[1] > 0].tolist() == [4.0, 5.0]
        # and the trainer sees the summed pairs: the numpy trainer over
        # the three unique pairs gives the same factors
        # (a ridge that conditions rows of one and two pairs at rank 4:
        # float32 then tracks float64 to 1e-4, and unsummed duplicates
        # move the factors by O(1))
        params = ALSParams(rank=4, num_iterations=2, lambda_=0.5, seed=7)
        Xn, Yn = numpy_train_als(
            np.asarray([0, 1, 1]), np.asarray([2, 0, 1]),
            np.asarray([3.0, 4.0, 5.0]), 2, 3, params)
        Xb, Yb = train_als_bucketed(
            side, bucket_ratings(cols, rows, vals, 3, 2), params)
        np.testing.assert_allclose(Xb, Xn, rtol=2e-3, atol=1e-4)
        np.testing.assert_allclose(Yb, Yn, rtol=2e-3, atol=1e-4)
