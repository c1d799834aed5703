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


# ---------------------------------------------------------------------------
# the assembly kernel (PR 44), interpreted here: where the Pallas solver
# runs, gather and normal equations come from
# als_pallas.assemble_normal_equations, batch-minor
# ---------------------------------------------------------------------------

def _assembly_problem(B, L, R, seed=0, n_cols=50):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    Y = jax.random.normal(k[0], (n_cols, R), jnp.float32)
    cols = jax.random.randint(k[1], (B, L), 0, n_cols)
    w = jax.random.uniform(k[2], (B, L), jnp.float32, -2.0, 5.0)
    m = (jax.random.uniform(k[3], (B, L)) < 0.8).astype(jnp.float32)
    m = m.at[1].set(0.0)                    # an all-padding row
    ridge = jnp.abs(jax.random.normal(k[4], (R,), jnp.float32))
    return Y, cols, w, m, ridge


def _kernel_equations(Y, cols, w, m, lam, alpha, implicit, ridge):
    """``A [B, R, R]``, ``b [B, R]`` as the kernel path assembles them,
    and the batch-minor arrays themselves."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import als, als_pallas

    B, R = cols.shape[0], Y.shape[1]
    wide, start = als._kernel_operands(Y, lam, implicit, None, ridge)
    wm = w * m
    aw, bw = als.implicit_weights(wm, alpha) if implicit else (m, wm)
    At, bt = als_pallas.assemble_normal_equations(wide, cols, aw, bw,
                                                  start, interpret=True)
    A = jnp.transpose(At[:, :, :B], (2, 0, 1))
    if not implicit:                        # ALS-WR's ridge, by row
        A = A + (lam * jnp.maximum(m.sum(axis=1), 1.0))[:, None, None] \
            * jnp.eye(R)
    return A, bt[:, :B].T, At, bt, start


def _float64_equations(Y, cols, w, m, lam, alpha, implicit, ridge):
    """The same equations in float64 numpy: what both float32
    assemblies are off from (at L 4,096 the einsum's ``b`` by 1.2e-6 of
    its largest entry, the kernel's by 1.5e-7)."""
    Y, w, m = (np.asarray(a, np.float64) for a in (Y, w, m))
    R, Yg, w = Y.shape[1], Y[np.asarray(cols)], w * m
    if implicit:
        aw = alpha * np.abs(w)
        bw = (w > 0) * (1.0 + aw)
        A = Y.T @ Y + lam * np.eye(R) \
            + np.einsum("bl,blr,bls->brs", aw, Yg, Yg)
    else:
        bw = w
        A = np.einsum("bl,blr,bls->brs", m, Yg, Yg) \
            + (lam * np.maximum(m.sum(axis=1), 1.0))[:, None, None] \
            * np.eye(R)
    if ridge is not None:
        A = A + np.diag(np.asarray(ridge, np.float64))
    return A, np.einsum("bl,blr->br", bw, Yg)


def _worst(got, want) -> float:
    """Largest difference as a share of the largest entry wanted."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / max(np.abs(want).max(), 1e-30))


@pytest.mark.pallas
class TestAssemblyKernel:
    # B 13: under one sublane tile of rows past 8, an all-padding row;
    # L 200 is a multiple of no tile, 4,096 is eight chunks of a row
    @pytest.mark.parametrize("with_ridge", [False, True],
                             ids=["", "extra_ridge"])
    @pytest.mark.parametrize("L", [16, 64, 200, 4096])
    @pytest.mark.parametrize("implicit", [True, False],
                             ids=["implicit", "explicit"])
    def test_kernel_matches_the_einsum_assembly(self, implicit, L,
                                                with_ridge):
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        B, R = 13, 16
        Y, cols, w, m, ridge = _assembly_problem(B, L, R, seed=L)
        ridge = ridge if with_ridge else None
        A, b, At, bt, start = _kernel_equations(Y, cols, w, m, 0.1, 1.5,
                                                implicit, ridge)
        A0, b0, _ = als._assemble_fp32(Y, jnp.take(Y, cols, axis=0), w, m,
                                       0.1, 1.5, implicit, None, ridge)
        A64, b64 = _float64_equations(Y, cols, w, m, 0.1, 1.5, implicit,
                                      ridge)
        # within 1e-6 of the exact sums, and of the einsum's as far as
        # the einsum is itself
        assert _worst(A, A64) <= 1e-6 and _worst(b, b64) <= 1e-6
        assert _worst(A, A0) <= 1e-6 + _worst(A0, A64)
        assert _worst(b, b0) <= 1e-6 + _worst(b0, b64)
        assert _worst(jnp.swapaxes(A, 1, 2), A) <= 1e-6
        # the all-padding row: what every row shares, and no right side
        assert float(jnp.abs(b[1]).max()) == 0.0
        np.testing.assert_array_equal(np.asarray(At[:, :, 1]),
                                      np.asarray(start[:, :R]))
        # the rows past B, up to the solver's block of 128: systems the
        # solver can take
        assert At.shape == (R, R, 128) and bt.shape == (R, 128)
        np.testing.assert_array_equal(
            np.asarray(At[:, :, B:]),
            np.broadcast_to(np.asarray(start[:, :R])[:, :, None],
                            (R, R, 128 - B)))
        assert float(jnp.abs(bt[:, B:]).max()) == 0.0

    @pytest.mark.parametrize("B,R", [(136, 8), (260, 10), (72, 50)])
    def test_rows_over_several_blocks_and_odd_ranks(self, B, R):
        """B 136 and 260: two and three solver blocks, the last a
        partial one whose input blocks past B do not exist; rank 10 and
        50: no multiple of the sublane tile."""
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        Y, cols, w, m, ridge = _assembly_problem(B, 64, R, seed=B)
        A, b, At, _, _ = _kernel_equations(Y, cols, w, m, 0.05, 2.0, True,
                                           ridge)
        A0, b0, _ = als._assemble_fp32(Y, jnp.take(Y, cols, axis=0), w, m,
                                       0.05, 2.0, True, None, ridge)
        assert At.shape[2] == -(-B // 128) * 128
        assert _worst(A, A0) <= 1e-6 and _worst(b, b0) <= 1e-6

    @pytest.mark.parametrize("implicit", [True, False],
                             ids=["implicit", "explicit"])
    def test_solve_rows_same_under_vmap_over_configs(self, implicit):
        """The grid trainer's use: one table of ratings, a factor set,
        a lambda, an alpha and a ridge a config."""
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        B, L, R, k = 24, 40, 8, 3
        _, cols, w, m, _ = _assembly_problem(B, L, R, seed=3)
        keys = jax.random.split(jax.random.PRNGKey(9), 2)
        Ys = jax.random.normal(keys[0], (k, 50, R), jnp.float32)
        lam = jnp.asarray([0.01, 0.1, 1.0], jnp.float32)
        alpha = jnp.asarray([0.5, 1.0, 4.0], jnp.float32)
        ridge = jnp.abs(jax.random.normal(keys[1], (k, R), jnp.float32))

        def solve(solver):
            return jax.vmap(
                lambda Yk, lk, ak, rk: als._solve_rows(
                    Yk, cols, w, m, lk, ak, implicit, None, solver,
                    "fp32", False, rk))(Ys, lam, alpha, ridge)

        got, want = solve("pallas"), solve("cho")
        assert got.shape == (k, B, R)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        assert float(jnp.abs(got[:, 1]).max()) == 0.0   # the empty row

    @pytest.mark.parametrize("solver,fell_back,precision,kernel", [
        ("pallas", False, "fp32", True), ("cho", False, "fp32", False),
        ("lanes", True, "fp32", False), ("pallas", False, "bf16", False)])
    def test_span_counts_the_systems_the_kernel_assembled(
            self, solver, fell_back, precision, kernel):
        from predictionio_tpu.ops.als import (
            SolverChoice, solve_span_attributes)

        attrs = solve_span_attributes(SolverChoice(solver, fell_back), 7,
                                      precision)
        assert attrs["assemble_systems_kernel"] == (7 if kernel else 0)
        assert attrs["solve_systems"] == 7
