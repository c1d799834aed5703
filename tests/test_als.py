"""ALS kernel tests: convergence, and numerics vs a plain-numpy
reference implementation of the same normal equations (capability parity
check for MLlib ALS.trainImplicit as used by the recommendation template)."""

import numpy as np
import pytest

from als_reference import numpy_half_step, train_from_triples
from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings,
    cosine_scores,
    predict_scores_for_user,
    top_k_items,
)

RNG = np.random.default_rng(42)


def synthetic_ratings(n_users=60, n_items=40, rank=4, density=0.3, seed=0):
    """Low-rank ground truth with observed mask — recoverable by ALS."""
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank))
    V = rng.normal(size=(n_items, rank))
    full = U @ V.T
    mask = rng.random((n_users, n_items)) < density
    rows, cols = np.nonzero(mask)
    # implicit: positive counts where the underlying affinity is high
    vals = np.where(full[rows, cols] > 0, 1.0 + full[rows, cols], 0.0)
    keep = vals > 0
    return rows[keep], cols[keep], vals[keep].astype(np.float32)


# None solves each bucket in one dispatch; 256 slots split the largest
# bucket of every fixture below (320 to 1,024 slots) into lax.map blocks
SLOT_BUDGETS = pytest.mark.parametrize("slot_budget", [None, 256])


class TestNumerics:
    @SLOT_BUDGETS
    @pytest.mark.parametrize("implicit", [True, False])
    def test_half_step_matches_numpy_reference(self, implicit,
                                               slot_budget):
        """The bucketed einsum solve, whole or in ``lax.map`` blocks,
        must agree with the dense per-row reference to float32
        tolerance: Hu-Koren-Volinsky with dislikes among the ratings,
        and ALS-WR's per-row ridge."""
        import jax.numpy as jnp
        from predictionio_tpu.ops.als import (
            _bucket_tables, _solve_side_bucketed)

        rows, cols, vals = synthetic_ratings(20, 15, 3, 0.4)
        vals = np.where(np.arange(len(vals)) % 5 == 0, -vals, vals)
        n_users, n_items, rank = 20, 15, 5
        Y = RNG.normal(size=(n_items, rank)).astype(np.float32)
        tables, = _bucket_tables(
            bucket_ratings(rows, cols, vals, n_users, n_items))
        assert tables[0][1].shape == (24, 8)
        got = np.asarray(_solve_side_bucketed(
            jnp.asarray(Y), tables, n_users, lam=0.1, alpha=1.0,
            implicit=implicit,
            slot_budget=slot_budget and 64))   # blocks of 8 rows
        want = numpy_half_step(Y, rows, cols, vals, n_users, 0.1, 1.0,
                               implicit=implicit)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_training_reduces_loss(self):
        rows, cols, vals = synthetic_ratings()
        n_users, n_items = 60, 40

        def implicit_loss(X, Y):
            P = np.zeros((n_users, n_items))
            P[rows, cols] = 1.0
            C = np.ones((n_users, n_items))
            C[rows, cols] += 1.0 * vals
            E = P - X @ Y.T
            return float((C * E * E).sum())

        params0 = ALSParams(rank=8, num_iterations=1, lambda_=0.01, seed=7)
        X1, Y1 = train_from_triples(rows, cols, vals, n_users, n_items,
                                    params0)
        params = ALSParams(rank=8, num_iterations=10, lambda_=0.01, seed=7)
        X, Y = train_from_triples(rows, cols, vals, n_users, n_items,
                                  params)
        assert implicit_loss(X, Y) < implicit_loss(X1, Y1) * 0.9

    @SLOT_BUDGETS
    def test_recovers_preferences(self, slot_budget):
        """Observed pairs must outscore unobserved ones on average."""
        rows, cols, vals = synthetic_ratings()
        n_users, n_items = 60, 40
        X, Y = train_from_triples(
            rows, cols, vals, n_users, n_items,
            ALSParams(rank=8, num_iterations=10, lambda_=0.05, seed=3,
                      bucket_slot_budget=slot_budget))
        S = X @ Y.T
        observed = np.zeros((n_users, n_items), dtype=bool)
        observed[rows, cols] = True
        assert S[observed].mean() > S[~observed].mean() + 0.2

    @SLOT_BUDGETS
    def test_explicit_mode(self, slot_budget):
        rows, cols, vals = synthetic_ratings()
        n_users, n_items = 60, 40
        X, Y = train_from_triples(
            rows, cols, vals, n_users, n_items,
            ALSParams(rank=8, num_iterations=10, lambda_=0.1,
                      implicit_prefs=False, seed=3,
                      bucket_slot_budget=slot_budget))
        pred = (X @ Y.T)[rows, cols]
        # explicit mode regresses the rating values themselves
        err = np.abs(pred - vals).mean() / vals.mean()
        assert err < 0.35

    @SLOT_BUDGETS
    def test_implicit_mode_negative_signal_stays_finite(self, slot_budget):
        """Implicit mode with negative ratings (dislikes): confidence uses
        |r|, preference r>0 — factors stay finite and dislikes score below
        likes (MLlib trainImplicit semantics)."""
        rng = np.random.default_rng(9)
        n_users, n_items = 40, 25
        rows = np.repeat(np.arange(n_users), 6)
        cols = rng.integers(0, n_items, rows.shape[0])
        vals = np.where(rng.random(rows.shape[0]) < 0.3, -5.0,
                        1.0 + 2 * rng.random(rows.shape[0])).astype(np.float32)
        X, Y = train_from_triples(
            rows, cols, vals, n_users, n_items,
            ALSParams(rank=6, num_iterations=8, lambda_=0.05, seed=1,
                      bucket_slot_budget=slot_budget))
        assert np.isfinite(X).all() and np.isfinite(Y).all()
        S = X @ Y.T
        # the tables sum duplicates, so score by the summed sign
        agg = {}
        for r, c, v in zip(rows, cols, vals):
            agg[(r, c)] = agg.get((r, c), 0.0) + v
        liked = np.array([S[r, c] for (r, c), v in agg.items() if v > 0])
        disliked = np.array([S[r, c] for (r, c), v in agg.items() if v < 0])
        assert liked.mean() > disliked.mean() + 0.2

    @SLOT_BUDGETS
    def test_explicit_mode_negative_and_zero_ratings(self, slot_budget):
        """Zero/negative explicit ratings are real observations, not
        padding: regression for the weights>0 masking bug."""
        rng = np.random.default_rng(5)
        n_users, n_items, rank = 30, 20, 4
        Xt = rng.normal(size=(n_users, rank))
        Yt = rng.normal(size=(n_items, rank))
        R = Xt @ Yt.T  # dense signed "ratings" incl. negatives
        rows, cols = np.nonzero(rng.random((n_users, n_items)) < 0.6)
        vals = R[rows, cols].astype(np.float32)
        assert (vals < 0).any()
        X, Y = train_from_triples(
            rows, cols, vals, n_users, n_items,
            ALSParams(rank=rank, num_iterations=10, lambda_=0.05,
                      implicit_prefs=False, seed=3,
                      bucket_slot_budget=slot_budget))
        pred = (X @ Y.T)[rows, cols]
        # negative ratings must be regressed toward negative predictions
        neg = vals < -0.5
        assert pred[neg].mean() < -0.2
        err = np.abs(pred - vals).mean() / np.abs(vals).mean()
        assert err < 0.35

    def test_deterministic_given_seed(self):
        rows, cols, vals = synthetic_ratings(20, 15, 3, 0.4)
        params = ALSParams(rank=4, num_iterations=3, seed=11)
        a = train_from_triples(rows, cols, vals, 20, 15, params)
        b = train_from_triples(rows, cols, vals, 20, 15, params)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


class TestScoring:
    def test_top_k(self):
        s = np.array([0.1, 0.9, 0.5, 0.7])
        idx, scores = top_k_items(s, 2)
        assert idx.tolist() == [1, 3]
        assert scores.tolist() == [pytest.approx(0.9), pytest.approx(0.7)]

    def test_cosine_scores_match_reference_formula(self):
        q = np.array([[1.0, 0.0], [0.0, 1.0]])
        items = np.array([[2.0, 0.0], [1.0, 1.0]])
        s = cosine_scores(q, items)
        # item0: cos=1 with q0, 0 with q1; item1: 1/sqrt2 each
        np.testing.assert_allclose(s, [1.0, np.sqrt(2)], atol=1e-6)

    def test_predict_scores(self):
        u = np.array([1.0, 2.0])
        items = np.array([[1.0, 0.0], [0.0, 1.0]])
        np.testing.assert_allclose(
            predict_scores_for_user(u, items), [1.0, 2.0])
