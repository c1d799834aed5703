"""Multi-device sharding tests on the virtual 8-device CPU mesh
(conftest.py sets xla_force_host_platform_device_count=8 — the local-mode
cluster substitution, SURVEY §4)."""

import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    train_als_bucketed,
)
from predictionio_tpu.parallel import data_parallel_mesh
from predictionio_tpu.parallel.als_sharding import (
    train_als_auto,
    train_als_bucketed_sharded,
    train_als_device,
)
from tests.test_als import synthetic_ratings

# multichip: rerunnable on a REAL mesh via `pytest -m multichip` on the
# bench host; tier-1 runs them on the virtual 8-device plane
pytestmark = pytest.mark.multichip


@pytest.fixture(scope="module")
def mesh8():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU scaffold")
    return data_parallel_mesh(8)


class TestMeshHelpers:
    def test_mesh_helpers(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        from predictionio_tpu.parallel.mesh import mesh_2d

        m = mesh_2d(4, 2)
        assert m.devices.shape == (4, 2)
        assert m.axis_names == ("data", "model")
        with pytest.raises(ValueError):
            mesh_2d(16, 16)


@pytest.fixture(scope="module", params=[(2, 4), (4, 2)])
def mesh2d(request):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU scaffold")
    from predictionio_tpu.parallel.mesh import mesh_2d

    d, m = request.param
    return mesh_2d(d, m)


class TestShardedBucketedALS:
    def test_matches_single_device_numerics(self, mesh8):
        rows, cols, vals = synthetic_ratings(50, 30, 4, 0.3)
        params = ALSParams(rank=6, num_iterations=4, lambda_=0.05, seed=5)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 50, 30)
        X1, Y1 = train_als_bucketed(ub, ib, params)
        X8, Y8 = train_als_bucketed_sharded(ub, ib, params, mesh8)
        assert X8.shape == X1.shape and Y8.shape == Y1.shape
        np.testing.assert_allclose(X8, X1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(Y8, Y1, rtol=1e-4, atol=1e-5)

    def test_uneven_rows_are_padded(self, mesh8):
        # 13 users over 8 devices: padding must not change results
        rows, cols, vals = synthetic_ratings(13, 9, 2, 0.5, seed=2)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 13, 9)
        params = ALSParams(rank=4, num_iterations=2, seed=1)
        X1, Y1 = train_als_bucketed(ub, ib, params)
        X8, Y8 = train_als_bucketed_sharded(ub, ib, params, mesh8)
        assert X8.shape == X1.shape and Y8.shape == Y1.shape
        np.testing.assert_allclose(X8, X1, rtol=1e-4, atol=1e-5)

    def test_auto_matches_single_device_numerics(self, mesh8):
        rows, cols, vals = synthetic_ratings(20, 12, 3, 0.4, seed=3)
        params = ALSParams(rank=4, num_iterations=2, seed=0)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 20, 12)
        Xa, Ya = train_als_auto(ub, ib, params)
        X1, Y1 = train_als_bucketed(ub, ib, params)
        np.testing.assert_allclose(Xa, X1, rtol=1e-4, atol=1e-5)


class TestShardedALS2D:
    """Factor matrices sharded over the model axis (the ALX layout)."""

    def test_matches_single_device_numerics(self, mesh2d):
        rows, cols, vals = synthetic_ratings(50, 30, 4, 0.3)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 50, 30)
        params = ALSParams(rank=6, num_iterations=4, lambda_=0.05, seed=5)

        X1, Y1 = train_als_bucketed(ub, ib, params)
        X2, Y2 = train_als_bucketed_sharded(
            ub, ib, params, mesh2d, factor_spec=P("model", None))
        np.testing.assert_allclose(X2, X1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(Y2, Y1, rtol=1e-4, atol=1e-5)

    def test_factors_stay_sharded_in_hbm(self, mesh2d):
        """``gather=False`` keeps the factor outputs sharded over the
        model axis — per-device factor memory is rows/model_size — and
        padded to that axis' size."""
        rows, cols, vals = synthetic_ratings(30, 15, 3, 0.4, seed=4)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 30, 15)
        Xo, Yo = train_als_bucketed_sharded(
            ub, ib, ALSParams(rank=4, num_iterations=1, seed=0), mesh2d,
            factor_spec=P("model", None), gather=False)
        assert Xo.sharding.spec == P("model", None)
        assert Yo.sharding.spec == P("model", None)
        m = mesh2d.shape["model"]
        assert Xo.shape == (-(-30 // m) * m, 4)
        assert Yo.shape == (-(-15 // m) * m, 4)
        assert {s.data.shape for s in Xo.addressable_shards} == \
            {(Xo.shape[0] // m, 4)}


# what each dispatcher is shown -> the trainer it takes, the devices its
# program spans, the solver PIO_ALS_SOLVER=pallas resolves there (the
# kernel on one device, `lanes` as its fallback on a mesh) and, for
# train_als_device, how the factors it returns are placed
DISPATCH = [
    ("auto", "one-device", "train_als_bucketed", None, "pallas", None),
    ("auto", "eight-devices", "train_als_bucketed_sharded", 8, "lanes",
     None),
    ("auto", "two-processes", "train_als_bucketed_sharded", 8, "lanes",
     None),
    ("device", "one-device", "train_als_bucketed_sharded", 1, "pallas",
     P(None, None)),
    ("device", "eight-devices", "train_als_bucketed_sharded", 8, "lanes",
     P("model", None)),
    ("device", "mesh8", "train_als_bucketed_sharded", 8, "lanes",
     P(None, None)),
]


class TestDispatch:
    @pytest.mark.parametrize(
        "dispatcher,shown,trainer,devices,solver,spec", DISPATCH,
        ids=[f"{d[0]}-{d[1]}" for d in DISPATCH])
    def test_says_which_trainer_and_solver_ran(
            self, monkeypatch, mesh8, dispatcher, shown, trainer, devices,
            solver, spec):
        import jax

        from predictionio_tpu.parallel import distributed
        from predictionio_tpu.utils import tracing

        meshes = []
        if shown == "one-device":
            one = jax.devices()[:1]
            monkeypatch.setattr(jax, "devices", lambda *a: one)
        elif shown == "two-processes":
            monkeypatch.setattr(jax, "process_count", lambda: 2)
        real = distributed.host_aware_mesh

        def recorded(*a, **kw):
            meshes.append(real(*a, **kw))
            return meshes[-1]
        monkeypatch.setattr(distributed, "host_aware_mesh", recorded)
        monkeypatch.setenv("PIO_ALS_SOLVER", "pallas")
        rows, cols, vals = synthetic_ratings(20, 12, 3, 0.4, seed=3)
        ub, ib = bucket_ratings_pair(rows, cols, vals, 20, 12)
        params = ALSParams(rank=4, num_iterations=2, seed=0)
        t0 = tracing.span_now()
        if dispatcher == "auto":
            X, Y = train_als_auto(ub, ib, params)
            assert isinstance(X, np.ndarray) and X.shape == (20, 4)
            # host_aware_mesh() is the multi-process runtime's alone
            assert len(meshes) == (shown == "two-processes")
        else:
            X, Y = train_als_device(
                ub, ib, params, mesh=mesh8 if shown == "mesh8" else None)
            assert X.sharding.spec == spec and Y.sharding.spec == spec
            want = {"one-device": [("data",)], "mesh8": [],
                    "eight-devices": [("data", "model")]}[shown]
            assert [m.axis_names for m in meshes] == want
            if want == [("data", "model")]:
                assert dict(meshes[0].shape) == {"data": 4, "model": 2}
        (root,) = tracing.trace_buffer().stage_summaries(
            t0, root="als.train")
        (sp,) = [s for s in tracing.trace_buffer().get(
            root["traceId"])["spans"] if s["name"] == "als.iterations"]
        systems = sp["attributes"]["solve_systems"]
        assert systems > 0
        ran = "train_als_bucketed_sharded" \
            if "devices" in sp["attributes"] else "train_als_bucketed"
        assert (ran, sp["attributes"].get("devices"),
                sp["attributes"]["solver"],
                sp["attributes"]["solve_systems_fallback"]) == (
            trainer, devices, solver,
            systems if solver == "lanes" else 0)
        # one solver or the other, the same factors
        monkeypatch.setenv("PIO_ALS_SOLVER", "cho")
        X1, _ = train_als_bucketed(ub, ib, params)
        np.testing.assert_allclose(np.asarray(X)[:20], X1, rtol=5e-3,
                                   atol=5e-4)
