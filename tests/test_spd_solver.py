"""Batched SPD solvers: the Pallas kernel (``als_pallas.spd_solve``, the
TPU default up to rank 96) and the batch-on-lanes blocked Cholesky
(``spd_solve_lanes``, what runs above that rank and in the sharded
trainers) must agree with LAPACK's cho_solve — the solver swap is what
buys the ALS epoch its largest single win on TPU (XLA's batched
Cholesky round-trips HBM per column; see ops/als.py:_spd_solve) — and
the resolver must name the one that runs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from als_reference import spd_solve_whole_block

from predictionio_tpu.ops.als import (
    ALSParams,
    _resolve_spd_solver,
    _spd_solve,
    _spd_solver_mode,
    bucket_ratings,
    bucket_ratings_pair,
    fold_in_users,
    spd_solve_lanes,
    train_als_bucketed,
)


def spd_systems(B, R, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.normal(size=(B, R, R)).astype(np.float32)
    A = M @ M.transpose(0, 2, 1) + R * np.eye(R, dtype=np.float32)
    b = rng.normal(size=(B, R)).astype(np.float32)
    return A, b


def ill_scaled_systems(B, R, seed=3):
    """Wide dynamic range of confidence weights -> wide A spectrum."""
    rng = np.random.default_rng(seed)
    M = rng.normal(size=(B, R, R)).astype(np.float32)
    scales = 10.0 ** rng.uniform(-2, 2, size=(B, 1, 1))
    A = ((M @ M.transpose(0, 2, 1)) * scales
         + 0.01 * np.eye(R, dtype=np.float32)).astype(np.float32)
    b = rng.normal(size=(B, R)).astype(np.float32)
    return A, b


def asymmetric_systems(B, R, seed=7):
    """``spd_systems`` with garbage below the diagonal: the kernel reads
    the upper triangle alone (the pivot row stands in for the column)."""
    A, b = spd_systems(B, R, seed)
    lower = np.tril_indices(R, -1)
    A[:, lower[0], lower[1]] = 50 * np.random.default_rng(seed).normal(
        size=(B, len(lower[0]))).astype(np.float32)
    return A, b


def rel_residual(A, x, b):
    res = np.einsum("brs,bs->br", A, x) - b
    return np.linalg.norm(res, axis=1) / np.linalg.norm(b, axis=1)


def small_ratings(seed=5, n_u=60, n_i=40, nnz=900):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_u, size=nnz), rng.integers(0, n_i, size=nnz),
            rng.integers(1, 6, size=nnz).astype(np.float32))


def small_bucketed(seed=5, n_u=60, n_i=40):
    rows, cols, vals = small_ratings(seed, n_u, n_i)
    return bucket_ratings_pair(rows, cols, vals, n_u, n_i)


class TestLanesSolver:
    @pytest.mark.parametrize("B,R", [(5, 8), (17, 16), (40, 64), (3, 10)])
    def test_matches_lapack(self, B, R):
        A, b = spd_systems(B, R)
        x = np.asarray(spd_solve_lanes(jnp.asarray(A), jnp.asarray(b)))
        want = np.asarray(jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(jnp.asarray(A)), jnp.asarray(b)))
        np.testing.assert_allclose(x, want, rtol=2e-3, atol=2e-4)

    def test_jit_traceable(self):
        A, b = spd_systems(12, 16)
        x = np.asarray(jax.jit(spd_solve_lanes)(jnp.asarray(A),
                                                jnp.asarray(b)))
        want = np.stack([np.linalg.solve(A[i], b[i]) for i in range(12)])
        np.testing.assert_allclose(x, want, rtol=2e-3, atol=2e-4)

    def test_ill_scaled_systems(self):
        A, b = ill_scaled_systems(20, 32)
        x = np.asarray(spd_solve_lanes(jnp.asarray(A), jnp.asarray(b)))
        assert rel_residual(A, x, b).max() < 1e-2


@pytest.mark.pallas
class TestPallasKernelInterpret:
    def test_matches_lapack_tiny(self):
        from predictionio_tpu.ops.als_pallas import spd_solve

        A, b = spd_systems(9, 8)
        x = np.asarray(spd_solve(jnp.asarray(A), jnp.asarray(b),
                                 interpret=True))
        want = np.stack([np.linalg.solve(A[i], b[i]) for i in range(9)])
        np.testing.assert_allclose(x, want, rtol=2e-3, atol=2e-4)

    # B off the kernel's 128-system block on both sides of it; rank 10
    # is the upstream default (a sublane count that is no multiple of
    # 8), 96 the largest the kernel takes
    @pytest.mark.parametrize("B,R", [(5, 8), (130, 10), (257, 64),
                                     (40, 96)])
    @pytest.mark.parametrize("systems", [spd_systems, ill_scaled_systems])
    def test_matches_lapack(self, B, R, systems):
        from predictionio_tpu.ops.als_pallas import spd_solve

        A, b = systems(B, R)
        x = np.asarray(spd_solve(jnp.asarray(A), jnp.asarray(b),
                                 interpret=True))
        assert x.shape == (B, R)
        if systems is ill_scaled_systems:
            # as TestLanesSolver: held to the residual, and to LAPACK's
            want = np.asarray(jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(jnp.asarray(A)),
                jnp.asarray(b)))
            assert rel_residual(A, x, b).max() < max(
                1e-2, 2 * rel_residual(A, want, b).max())
        else:
            want = np.linalg.solve(A.astype(np.float64),
                                   b.astype(np.float64)[..., None])[..., 0]
            np.testing.assert_allclose(x, want, rtol=2e-3, atol=2e-4)

    # same shapes as above; PR 49 cut the trailing update down to the
    # entries a later step reads, and nothing else may have moved
    @pytest.mark.parametrize("B,R", [(5, 8), (130, 10), (257, 64),
                                     (40, 96)])
    @pytest.mark.parametrize("systems", [spd_systems, ill_scaled_systems,
                                         asymmetric_systems])
    def test_equals_the_whole_block_recurrence_to_the_bit(self, B, R,
                                                          systems):
        from predictionio_tpu.ops.als_pallas import spd_solve

        A, b = (jnp.asarray(a) for a in systems(B, R))
        x = np.asarray(spd_solve(A, b, interpret=True))
        want = np.asarray(spd_solve_whole_block(A, b))
        assert np.isfinite(want).all()
        np.testing.assert_array_equal(x.view(np.int32),
                                      want.view(np.int32))

    # every equation of the kernel's body is traced and lowered once a
    # bucket, 21 solve kernels in the ML-20M program, at 0.1-0.2 ms
    # each: `setup_s` is bound at 10% and refused PR 41 on 0.73 s. The
    # ceilings are what PR 49 landed with (90 and 112) plus a tenth; the
    # whole-block kernel before it had 56 at every rank, as this one
    # still has up to rank 16
    @pytest.mark.parametrize("R,ceiling", [(64, 99), (96, 123)])
    def test_kernel_body_stays_small(self, R, ceiling):
        from predictionio_tpu.ops.als_pallas import _SPD_BB, _build_spd

        def equations(jaxpr):
            n = len(jaxpr.eqns)
            for eqn in jaxpr.eqns:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    n += equations(sub)
            return n

        outer = jax.make_jaxpr(_build_spd(_SPD_BB, R, True))(
            jax.ShapeDtypeStruct((R, R, _SPD_BB), jnp.float32),
            jax.ShapeDtypeStruct((R, _SPD_BB), jnp.float32))
        (call,) = [e for e in outer.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        assert equations(call.params["jaxpr"]) <= ceiling

    def test_rank_128_takes_lanes_and_is_named(self, monkeypatch):
        """Above the kernel's rank the resolver says ``lanes`` — in the
        jit's statics, the fingerprint and the span — and ``_spd_solve``
        refuses a ``pallas`` that was not resolved with the rank."""
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        A, b = (jnp.asarray(a) for a in spd_systems(6, 128))
        assert _spd_solver_mode(96, (A, b)) == "pallas"
        assert _resolve_spd_solver(128, (A, b)) == ("lanes", True)
        x = np.asarray(_spd_solve(A, b, _spd_solver_mode(128, (A, b))))
        np.testing.assert_array_equal(x, np.asarray(spd_solve_lanes(A, b)))
        with pytest.raises(ValueError, match="_resolve_spd_solver"):
            _spd_solve(A, b, "pallas")


def spanning(n_devices: int, replicated: bool = False):
    """A program's operands, one of them living on ``n_devices`` of the
    virtual mesh (row-sharded, or a copy on each), one on the host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("d",))
    table = jax.device_put(
        np.zeros((8, 4), np.float32),
        NamedSharding(mesh, P() if replicated else P("d", None)))
    return (table, np.zeros(3, np.int32))


class TestResolver:
    @pytest.mark.parametrize("backend,rank,n_devices,want", [
        ("tpu", 8, 1, "pallas"), ("tpu", 10, 1, "pallas"),
        ("tpu", 96, 1, "pallas"), ("tpu", 97, 1, "lanes"),
        ("tpu", 128, 1, "lanes"), ("tpu", 64, 4, "lanes"),
        ("cpu", 64, 1, "cho"), ("cpu", 128, 1, "cho"),
        ("gpu", 64, 1, "cho"), ("cpu", 64, 4, "cho")])
    def test_platform_and_shape_decide(self, monkeypatch, backend, rank,
                                       n_devices, want):
        operands = spanning(n_devices)
        monkeypatch.delenv("PIO_ALS_SOLVER", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert _spd_solver_mode(rank, operands) == want

    @pytest.mark.parametrize("operands,want", [
        (lambda: spanning(2), ("lanes", True)),
        (lambda: spanning(4, replicated=True), ("lanes", True)),
        (lambda: spanning(1), ("pallas", False)),
        (lambda: (np.zeros((8, 4), np.float32),), ("pallas", False)),
        (lambda: (jax.ShapeDtypeStruct((8, 4), np.float32),),
         ("pallas", False))],
        ids=["sharded", "replicated", "one-device", "host", "abstract"])
    def test_device_count_is_read_off_the_operands(self, monkeypatch,
                                                   operands, want):
        """A table on several devices, sharded or copied, makes its jit
        a partitioned program: no caller has to say so."""
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        assert _resolve_spd_solver(64, operands()) == want

    @pytest.mark.parametrize("forced,rank,want", [
        ("lanes", 8, "lanes"), ("cho", 64, "cho"), ("xla", 64, "cho"),
        ("pallas", 64, "pallas"), ("pallas", 128, "lanes")])
    def test_env_keeps_its_four_values(self, monkeypatch, forced, rank,
                                       want):
        monkeypatch.setenv("PIO_ALS_SOLVER", forced)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert _spd_solver_mode(rank, ()) == want

    @pytest.mark.parametrize("rank,solver,fallback", [
        (8, "pallas", False), (100, "lanes", True)])
    def test_iterations_span_says_what_ran(self, monkeypatch, rank,
                                           solver, fallback):
        from predictionio_tpu.utils import tracing

        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        us, its = small_bucketed()
        t0 = tracing.span_now()
        train_als_bucketed(us, its, ALSParams(rank=rank, num_iterations=2,
                                              seed=2))
        (root,) = tracing.trace_buffer().stage_summaries(
            t0, root="als.train")
        (sp,) = [s for s in tracing.trace_buffer().get(
            root["traceId"])["spans"] if s["name"] == "als.iterations"]
        systems = 2 * sum(b.cols.shape[0]
                          for b in us.buckets + its.buckets)
        assert sp["attributes"] == {
            "solver": solver, "solve_systems": systems,
            "solve_systems_fallback": systems if fallback else 0,
            "assemble_systems_kernel": 0 if fallback else systems}

    def test_forced_lanes_is_no_fallback(self, monkeypatch):
        from predictionio_tpu.ops.als import solve_span_attributes

        monkeypatch.setenv("PIO_ALS_SOLVER", "lanes")
        choice = _resolve_spd_solver(128, spanning(4))
        assert choice == ("lanes", False)
        assert solve_span_attributes(choice, 7) == {
            "solver": "lanes", "solve_systems": 7,
            "solve_systems_fallback": 0, "assemble_systems_kernel": 0}

    def test_fold_in_span_says_what_ran(self, monkeypatch):
        from predictionio_tpu.utils import tracing

        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        Y = np.random.default_rng(0).normal(size=(30, 8)).astype(np.float32)
        t0 = tracing.span_now()
        with tracing.trace_scope("test.foldin", slow_exempt=True):
            fold_in_users(Y, [np.array([1, 4, 7])], [np.ones(3, np.float32)],
                          ALSParams(rank=8))
        (root,) = tracing.trace_buffer().stage_summaries(
            t0, root="test.foldin")
        (sp,) = [s for s in tracing.trace_buffer().get(
            root["traceId"])["spans"] if s["name"] == "device.execute"]
        assert sp["attributes"]["solver"] == "pallas"
        assert sp["attributes"]["solve_systems"] == 8   # the row bucket
        assert sp["attributes"]["solve_systems_fallback"] == 0
        assert sp["attributes"]["lane"] == "foldin"


class TestSolverSwapPreservesTraining:
    def test_bucketed_training_same_under_lanes_solver(self, monkeypatch):
        """Training through the lanes solver must land on the same
        factors as the LAPACK path — the TPU default is only a faster
        implementation of the identical math."""
        rows, cols, vals = small_ratings()
        params = ALSParams(rank=8, num_iterations=2, seed=2)

        def train_both(flavor):
            # solver mode is resolved per train_als* call and passed as a
            # static jit arg — flipping the env var between trainings
            # must take effect WITHOUT clearing any jit cache
            monkeypatch.setenv("PIO_ALS_SOLVER", flavor)
            return train_als_bucketed(
                bucket_ratings(rows, cols, vals, 60, 40),
                bucket_ratings(cols, rows, vals, 40, 60), params)

        cho = train_both("cho")
        lanes = train_both("lanes")
        monkeypatch.delenv("PIO_ALS_SOLVER")
        for got, want in zip(lanes, cho):
            np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-4)

    # the TPU's resolved choice, run here in interpret mode: every
    # caller that reaches the kernel on a TPU agrees with LAPACK to the
    # tolerance of the lanes swap above
    @pytest.mark.pallas
    @pytest.mark.parametrize("slot_budget", [None, 256])
    def test_bucketed_training_same_under_pallas_solver(self, monkeypatch,
                                                        slot_budget):
        """A dozen buckets a side, each its own B and none a multiple
        of 128; with a slot budget the large ones go through
        ``lax.map`` blocks."""
        us, its = small_bucketed()
        params = ALSParams(rank=8, num_iterations=2, seed=2,
                           bucket_slot_budget=slot_budget,
                           solve_refine=slot_budget is not None)
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        want = train_als_bucketed(us, its, params)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        got = train_als_bucketed(us, its, params)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=5e-3, atol=5e-4)

    @pytest.mark.pallas
    def test_grid_vmap_same_under_pallas_solver(self, monkeypatch):
        """The config grid vmaps the half-steps: ``pallas_call`` under
        ``jax.vmap``, with a rank-padded config in the batch."""
        from predictionio_tpu.ops.tuning import (
            make_grid,
            train_als_grid_bucketed,
        )

        us, its = small_bucketed()
        grid = make_grid(ALSParams(rank=6, num_iterations=2, seed=3),
                         [{"lambda": 0.01}, {"lambda": 0.3, "alpha": 5.0},
                          {"rank": 4}])
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        want = train_als_grid_bucketed(us, its, grid)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        got = train_als_grid_bucketed(us, its, grid)
        assert got.alive.all()
        np.testing.assert_allclose(got.user_factors, want.user_factors,
                                   rtol=5e-3, atol=5e-4)
        np.testing.assert_allclose(got.item_factors, want.item_factors,
                                   rtol=5e-3, atol=5e-4)
        assert not got.user_factors[2, :, 4:].any()   # pads exact zeros

    @pytest.mark.pallas
    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    def test_fold_in_same_under_pallas_solver(self, monkeypatch,
                                              precision):
        """A handful of systems: one grid step of 128."""
        rng = np.random.default_rng(1)
        Y = rng.normal(size=(40, 10)).astype(np.float32) / np.sqrt(10)
        cols = [rng.choice(40, size=n, replace=False) for n in (3, 17, 1)]
        vals = [rng.integers(1, 6, size=len(c)).astype(np.float32)
                for c in cols]
        params = ALSParams(rank=10, precision=precision)
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        want = fold_in_users(Y, cols, vals, params)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        got = fold_in_users(Y, cols, vals, params)
        assert got.shape == (3, 10)
        tol = dict(rtol=5e-3, atol=5e-4) if precision == "fp32" \
            else dict(rtol=3e-2, atol=3e-3)
        np.testing.assert_allclose(got, want, **tol)

    def test_sharded_trainer_keeps_lanes(self, monkeypatch):
        """The sharded trainer is a jit over sharded tables, and the TPU
        compiler will not partition a Mosaic call: on a mesh of several
        devices the kernel must never be reached, whatever was asked."""
        from predictionio_tpu.ops import als_pallas
        from predictionio_tpu.parallel import (
            data_parallel_mesh,
            train_als_bucketed_sharded,
        )

        def refuse(*a, **k):
            raise AssertionError("the kernel was reached under a mesh")

        rows, cols, vals = small_ratings()
        params = ALSParams(rank=8, num_iterations=2, seed=2)
        sides = bucket_ratings_pair(rows, cols, vals, 60, 40)
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        want = train_als_bucketed(*sides, params)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        monkeypatch.setattr(als_pallas, "spd_solve", refuse)
        got = train_als_bucketed_sharded(*sides, params,
                                         data_parallel_mesh(8))
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=5e-3, atol=5e-4)

    def test_fold_in_against_a_store_on_a_mesh_keeps_lanes(self,
                                                           monkeypatch):
        """The serving store's item factors may be sharded over the
        mesh: the fold-in jit is then a partitioned program, and the
        resolver sees that in ``Y`` itself."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from predictionio_tpu.ops import als_pallas
        from predictionio_tpu.utils import tracing

        def refuse(*a, **k):
            raise AssertionError("the kernel was reached under a mesh")

        rng = np.random.default_rng(1)
        Y = rng.normal(size=(40, 8)).astype(np.float32) / np.sqrt(8)
        cols = [rng.choice(40, size=n, replace=False) for n in (3, 17)]
        vals = [np.ones(len(c), np.float32) for c in cols]
        params = ALSParams(rank=8)
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        want = fold_in_users(Y, cols, vals, params)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        monkeypatch.setattr(als_pallas, "spd_solve", refuse)
        Ys = jax.device_put(Y, NamedSharding(
            Mesh(np.array(jax.devices()[:4]), ("data",)), P("data", None)))
        t0 = tracing.span_now()
        with tracing.trace_scope("test.foldin", slow_exempt=True):
            got = fold_in_users(Ys, cols, vals, params)
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-4)
        (root,) = tracing.trace_buffer().stage_summaries(
            t0, root="test.foldin")
        (sp,) = [s for s in tracing.trace_buffer().get(
            root["traceId"])["spans"] if s["name"] == "device.execute"]
        assert (sp["attributes"]["solver"],
                sp["attributes"]["solve_systems_fallback"]) == ("lanes", 8)

    def test_side_with_no_ratings_keeps_zero_factors(self):
        """No bucket on a side: nothing to scatter, zero factors out."""
        none = np.zeros(0, np.int32)
        us, its = bucket_ratings_pair(none, none, np.zeros(0, np.float32),
                                      5, 4)
        assert not us.buckets and not its.buckets
        X, Y = train_als_bucketed(us, its, ALSParams(rank=4,
                                                     num_iterations=2))
        assert X.shape == (5, 4) and Y.shape == (4, 4)
        assert not X.any() and not Y.any()

    def test_unknown_solver_mode_fails_loudly(self, monkeypatch):
        """A typo'd PIO_ALS_SOLVER must raise, not silently fall back."""
        monkeypatch.setenv("PIO_ALS_SOLVER", "turbo")
        with pytest.raises(ValueError, match="PIO_ALS_SOLVER"):
            _spd_solver_mode(8, ())
