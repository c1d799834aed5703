"""Length-bucketed ALS: numerics identical to the uniform padded path,
occupancy several-fold better on power-law data, nothing truncated by
default (100% unique-pair coverage — MLlib's full-RDD semantics,
custom-query ALSAlgorithm.scala:64-71)."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings,
    dedup_sum_ratings,
    pad_ratings,
    train_als,
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

    def test_occupancy_beats_uniform_padding(self):
        rows, cols, vals = powerlaw_triples(n_users=800, n_items=600,
                                            nnz=8000)
        b = bucket_ratings(rows, cols, vals, 800, 600)
        uniform = pad_ratings(rows, cols, vals, 800, 600)
        uniform_slots = uniform.cols.size
        assert b.padded_slots < uniform_slots / 3
        assert b.occupancy > 0.3

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
    @pytest.mark.parametrize("implicit", [True, False])
    def test_matches_uniform_path(self, implicit):
        rows, cols, vals = powerlaw_triples()
        params = ALSParams(rank=8, num_iterations=3, lambda_=0.05,
                           alpha=1.0, implicit_prefs=implicit, seed=4)
        Xu, Yu = train_als(pad_ratings(rows, cols, vals, 220, 90),
                           pad_ratings(cols, rows, vals, 90, 220), params)
        Xb, Yb = train_als_bucketed(
            bucket_ratings(rows, cols, vals, 220, 90),
            bucket_ratings(cols, rows, vals, 90, 220), params)
        # triaged (PR 6): the two layouts batch the einsums differently
        # (per-bucket vs one table), so fp32 reduction order differs;
        # on this CPU/BLAS the explicit lane (ALS-WR lambda*n scaling,
        # larger dynamic range) left 3/1760 entries at rel ~3e-3 vs the
        # old 2e-4 gate. 5e-3 still fails loudly on any real layout bug
        # (those diverge by O(1)).
        np.testing.assert_allclose(Xb, Xu, rtol=5e-3, atol=2e-5)
        np.testing.assert_allclose(Yb, Yu, rtol=5e-3, atol=2e-5)

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

    def test_duplicates_summed_like_uniform(self):
        rows = np.asarray([0, 0, 1, 1, 1])
        cols = np.asarray([2, 2, 0, 0, 1])
        vals = np.asarray([1.0, 2.0, 3.0, 1.0, 5.0], dtype=np.float32)
        params = ALSParams(rank=4, num_iterations=2, seed=7)
        Xu, Yu = train_als(pad_ratings(rows, cols, vals, 2, 3),
                           pad_ratings(cols, rows, vals, 3, 2), params)
        Xb, Yb = train_als_bucketed(
            bucket_ratings(rows, cols, vals, 2, 3),
            bucket_ratings(cols, rows, vals, 3, 2), params)
        np.testing.assert_allclose(Xb, Xu, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(Yb, Yu, rtol=1e-5, atol=1e-6)
