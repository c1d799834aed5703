"""Mixed-precision ALS policy tests (ops/als.py precision plumbing,
bf16-vs-fp32 differential numerics, carry-buffer donation, and the
slow-marked Precision@10 quality gate).

The policy contract: ``fp32`` (default) is byte-identical to the
historical all-fp32 pipeline; ``bf16`` stores/gathers the factor
matrices as bfloat16 while the normal-equation einsums and shared Gram
matrix accumulate in fp32 (``preferred_element_type``) and the batched
Cholesky solve stays fp32 — the ALX §4 storage/compute split."""

import dataclasses as dc

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops.als import (
    ALSParams,
    _als_iterations_bucketed,
    _als_precision_mode,
    _spd_solver_mode,
    bucket_ratings,
    bucket_ratings_pair,
    init_factors,
    train_als_bucketed,
)

# bf16 has an 8-bit mantissa: one rounding of the factor inputs costs a
# relative eps of 2^-8 per half-step; the fp32 accumulators keep the
# error from growing with row length L, so over k alternating
# iterations the factor error stays O(k * eps). The bound below gives
# ~4x headroom over that at the iteration counts used here (measured
# ~1.2 * EPS_BF16 after 3 iterations).
EPS_BF16 = 2.0 ** -8


def random_stream(n_users, n_items, nnz, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_users, size=nnz)
    cols = rng.integers(0, n_items, size=nnz)
    vals = rng.integers(1, 6, size=nnz).astype(np.float32)
    return rows, cols, vals


def rel_err(got, want):
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want))
                 / np.linalg.norm(np.asarray(want)))


class TestPolicyPlumbing:
    def test_unknown_env_value_raises(self, monkeypatch):
        """A typo'd PIO_ALS_PRECISION must raise, not silently fall
        back (mirror of the PIO_ALS_SOLVER contract)."""
        monkeypatch.setenv("PIO_ALS_PRECISION", "fp8")
        with pytest.raises(ValueError, match="PIO_ALS_PRECISION"):
            _als_precision_mode()

    def test_unknown_params_value_raises(self, monkeypatch):
        monkeypatch.delenv("PIO_ALS_PRECISION", raising=False)
        with pytest.raises(ValueError, match="ALSParams.precision"):
            _als_precision_mode(ALSParams(precision="fp16"))

    def test_unknown_value_raises_at_train(self, monkeypatch):
        monkeypatch.delenv("PIO_ALS_PRECISION", raising=False)
        rows, cols, vals = random_stream(20, 15, 100, 0)
        with pytest.raises(ValueError, match="precision"):
            train_als_bucketed(
                *bucket_ratings_pair(rows, cols, vals, 20, 15),
                ALSParams(rank=4, num_iterations=1, precision="turbo"))

    def test_env_overrides_params(self, monkeypatch):
        monkeypatch.setenv("PIO_ALS_PRECISION", "bf16")
        assert _als_precision_mode(ALSParams(precision="fp32")) == "bf16"
        monkeypatch.setenv("PIO_ALS_PRECISION", "fp32")
        assert _als_precision_mode(ALSParams(precision="bf16")) == "fp32"

    def test_env_change_between_trainings_takes_effect(self, monkeypatch):
        """Precision is resolved per train_als* call and passed as a
        static jit arg — flipping the env var between trainings must
        take effect WITHOUT clearing any jit cache (regression mirror
        of the PIO_ALS_SOLVER trace-time-read test)."""
        rows, cols, vals = random_stream(40, 25, 400, 3)
        us, its = bucket_ratings_pair(rows, cols, vals, 40, 25)
        params = ALSParams(rank=8, num_iterations=3, seed=2)

        monkeypatch.delenv("PIO_ALS_PRECISION", raising=False)
        X32, _ = train_als_bucketed(us, its, params)
        monkeypatch.setenv("PIO_ALS_PRECISION", "bf16")
        Xenv, _ = train_als_bucketed(us, its, params)
        monkeypatch.delenv("PIO_ALS_PRECISION")
        Xpar, _ = train_als_bucketed(
            us, its, dc.replace(params, precision="bf16"))

        # env-forced bf16 runs the exact program the params ask for...
        np.testing.assert_array_equal(Xenv, Xpar)
        # ...and it is genuinely the OTHER lane, not the cached fp32 one
        assert not np.array_equal(Xenv, X32)
        # flipping back re-selects the fp32 program bit-exactly
        X32b, _ = train_als_bucketed(us, its, params)
        np.testing.assert_array_equal(X32, X32b)

    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    def test_bucketed_carry_buffers_are_donated(self, precision):
        """The X/Y carries of the jitted iteration loop are donated:
        after a train step the INPUT factor buffers must be invalidated
        (their HBM was reused for the outputs) — the no-copy contract
        the steady-state epoch rate depends on."""
        rows, cols, vals = random_stream(40, 25, 400, 1)
        ub = bucket_ratings(rows, cols, vals, 40, 25)
        ib = bucket_ratings(cols, rows, vals, 25, 40)
        as_tuples = lambda s: tuple(  # noqa: E731
            (b.row_ids, b.cols, b.weights, b.mask) for b in s.buckets)
        X, Y = init_factors(40, 25, 8, 0)
        if precision == "bf16":
            X, Y = X.astype(jnp.bfloat16), Y.astype(jnp.bfloat16)
        Xn, _ = _als_iterations_bucketed(
            X, Y, as_tuples(ub), as_tuples(ib),
            lam=0.01, alpha=1.0, implicit=True, num_iterations=1,
            slot_budget=None, solver=_spd_solver_mode(8, (X, Y)),
            precision=precision, refine=False)
        assert X.is_deleted() and Y.is_deleted()
        assert np.isfinite(np.asarray(Xn, dtype=np.float32)).all()
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(X)

    def test_host_factors_always_fp32(self):
        """Whatever the training policy, gathered host factors land
        float32 — persistence/serving/eval stay byte-compatible."""
        rows, cols, vals = random_stream(30, 20, 200, 4)
        X, Y = train_als_bucketed(
            *bucket_ratings_pair(rows, cols, vals, 30, 20),
            ALSParams(rank=4, num_iterations=2, seed=1,
                      precision="bf16"))
        assert X.dtype == np.float32 and Y.dtype == np.float32


class TestDifferentialNumerics:
    @pytest.mark.parametrize("seed", [1, 11])
    def test_bucketed_bf16_close_to_fp32(self, seed):
        rows, cols, vals = random_stream(80, 50, 1500, seed)
        ub = bucket_ratings(rows, cols, vals, 80, 50)
        ib = bucket_ratings(cols, rows, vals, 50, 80)
        params = ALSParams(rank=8, num_iterations=3, seed=2)
        X32, Y32 = train_als_bucketed(ub, ib, params)
        X16, Y16 = train_als_bucketed(
            ub, ib, dc.replace(params, precision="bf16"))
        iters = params.num_iterations
        assert rel_err(X16, X32) < 4 * iters * EPS_BF16
        assert rel_err(Y16, Y32) < 4 * iters * EPS_BF16

    def test_explicit_mode_bf16(self):
        """The explicit ALS-WR lane under bf16 still regresses the
        ratings (same acceptance the fp32 lane's test uses)."""
        rng = np.random.default_rng(5)
        n_users, n_items, rank = 30, 20, 4
        Xt = rng.normal(size=(n_users, rank))
        Yt = rng.normal(size=(n_items, rank))
        R = Xt @ Yt.T
        rows, cols = np.nonzero(rng.random((n_users, n_items)) < 0.6)
        vals = R[rows, cols].astype(np.float32)
        X, Y = train_als_bucketed(
            *bucket_ratings_pair(rows, cols, vals, n_users, n_items),
            ALSParams(rank=rank, num_iterations=10, lambda_=0.05,
                      implicit_prefs=False, seed=3, precision="bf16"))
        pred = (X @ Y.T)[rows, cols]
        err = np.abs(pred - vals).mean() / np.abs(vals).mean()
        assert err < 0.35

    def test_solve_refine_knob(self):
        """solve_refine=True (one fp32 refinement pass per solve) must
        trace, stay finite, and land within the same bf16-vs-fp32 band —
        it tightens the solve residual, never degrades it."""
        rows, cols, vals = random_stream(60, 40, 900, 9)
        us, its = bucket_ratings_pair(rows, cols, vals, 60, 40)
        params = ALSParams(rank=8, num_iterations=3, seed=2)
        X32, _ = train_als_bucketed(us, its, params)
        Xr, Yr = train_als_bucketed(us, its, dc.replace(
            params, precision="bf16", solve_refine=True))
        assert np.isfinite(Xr).all() and np.isfinite(Yr).all()
        assert rel_err(Xr, X32) < 4 * params.num_iterations * EPS_BF16

    def test_sharded_bf16_close_to_fp32(self):
        """The mesh-sharded trainer under bf16 stays in the same band
        as the single-device lane (virtual 8-device CPU mesh)."""
        from predictionio_tpu.parallel.als_sharding import (
            train_als_bucketed_sharded,
        )
        from predictionio_tpu.parallel.mesh import data_parallel_mesh

        rows, cols, vals = random_stream(64, 40, 900, 2)
        us, its = bucket_ratings_pair(rows, cols, vals, 64, 40)
        params = ALSParams(rank=8, num_iterations=2, seed=2)
        X32, _ = train_als_bucketed(us, its, params)
        Xs, Ys = train_als_bucketed_sharded(
            us, its, dc.replace(params, precision="bf16"),
            data_parallel_mesh())
        assert Xs.dtype == np.float32
        assert rel_err(Xs, X32) < 4 * params.num_iterations * EPS_BF16


@pytest.mark.slow
class TestQualityGate:
    def test_bf16_precision_at_10_within_gate(self):
        """The hard gate the bf16 policy ships behind: Precision@10 on
        the ml100k-shaped leave-last-out protocol drops at most 0.02
        absolute vs the fp32 lane (quality_gates.run_precision_check)."""
        import quality_gates

        out = quality_gates.run_precision_check()
        assert out["bf16_precision_at_10"] >= \
            out["fp32_precision_at_10"] - 0.02, out

    def test_int8_serving_precision_at_10_within_gate(self):
        """The same hard gate for the int8 SERVING lane (ISSUE-11):
        scoring through the symmetric per-row absmax round-trip drops
        Precision@10 at most 0.02 absolute vs fp32."""
        import quality_gates

        out = quality_gates.run_precision_check()
        assert out["int8_serving_precision_at_10"] >= \
            out["fp32_precision_at_10"] - 0.02, out
