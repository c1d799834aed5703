"""ISSUE 15 — sharded live plane differential suite.

Density-aware item sharding (greedy bin-pack over the power-law head),
serving over a mesh-sharded factor store (per-shard top-k + on-device
log-tree merge, all precision lanes + the per-shard fused kernel),
sharded fold-in (patch + growth-by-resharding), the per-shard HBM
report, and the deployed fold-in freshness path against a sharded
store. Every gate is a differential against the single-chip path on
the conftest-forced 8 virtual CPU devices.
"""

import datetime as dt
import http.client
import json
import time
import urllib.parse

import numpy as np
import pytest

from predictionio_tpu.ops.serving import DeviceTopK
from predictionio_tpu.parallel.als_sharding import (
    ItemShardLayout,
    contiguous_item_layout,
    density_aware_item_layout,
)

pytestmark = pytest.mark.multichip

UTC = dt.timezone.utc


def _power_law_counts(n_items, nnz, seed=0, exp=0.8):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_items + 1) ** exp
    p /= p.sum()
    return np.bincount(rng.choice(n_items, size=nnz, p=p),
                       minlength=n_items).astype(np.int64)


# ---------------------------------------------------------------------------
# The layout itself
# ---------------------------------------------------------------------------

class TestItemShardLayout:
    def test_permutation_is_a_bijection_over_items(self):
        counts = _power_law_counts(37, 5000)
        lay = density_aware_item_layout(counts, 4)
        real = lay.perm[lay.perm >= 0]
        assert sorted(real.tolist()) == list(range(37))
        # inverse really inverts
        assert (lay.perm[lay.inv] == np.arange(37)).all()
        assert lay.n_positions % lay.n_shards == 0

    def test_capacity_bound_holds(self):
        counts = _power_law_counts(50, 4000)
        lay = density_aware_item_layout(counts, 4)
        assert (lay.items_per_shard <= lay.cap).all()
        assert int(lay.items_per_shard.sum()) == 50

    def test_beats_contiguous_on_power_law(self):
        """The point of the bin-pack: the head must not hot-spot one
        shard. On MovieLens-shaped popularity the contiguous layout's
        max/mean interaction mass is far above 1; the density-aware
        one sits near 1."""
        counts = _power_law_counts(400, 100_000)
        dense = density_aware_item_layout(counts, 4)
        spans = contiguous_item_layout(400, 4, counts=counts)
        d = dense.balance_report()["maxOverMeanInteractions"]
        c = spans.balance_report()["maxOverMeanInteractions"]
        assert c > 1.5          # the failure mode exists on this data
        assert d < 1.05         # and the bin-pack removes it
        assert d < c

    def test_zero_counts_degenerate(self):
        lay = density_aware_item_layout(np.zeros(10, np.int64), 4)
        assert int(lay.items_per_shard.sum()) == 10

    def test_json_round_trip(self):
        counts = _power_law_counts(23, 900)
        lay = density_aware_item_layout(counts, 4)
        back = ItemShardLayout.from_json(
            json.loads(json.dumps(lay.to_json())))
        assert (back.perm == lay.perm).all()
        assert (back.inv == lay.inv).all()
        assert back.n_shards == lay.n_shards
        assert (back.counts_per_shard == lay.counts_per_shard).all()

    def test_valid_mask_marks_pad_slots(self):
        lay = density_aware_item_layout(_power_law_counts(10, 100), 4)
        v = lay.valid_mask()
        assert v.sum() == 10
        assert ((lay.perm >= 0) == (v > 0)).all()


# ---------------------------------------------------------------------------
# Sharded serving differentials: sharded == single-chip on every lane
# ---------------------------------------------------------------------------

def _make_problem(seed=1, n=24, m=41, r=8):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, r)).astype(np.float32)
    Y = rng.normal(size=(m, r)).astype(np.float32)
    seen = {u: rng.choice(m, size=5, replace=False) for u in range(n)}
    return X, Y, seen


def _pair(X, Y, seen, layout, **kw):
    single = DeviceTopK(X, Y, {u: v.copy() for u, v in seen.items()},
                        microbatch=False, **kw)
    sharded = DeviceTopK(X, Y, {u: v.copy() for u, v in seen.items()},
                         microbatch=False, item_layout=layout, **kw)
    assert sharded.shard_count == layout.n_shards
    return single, sharded


def _layout_from_seen(seen, m, shards=4):
    counts = np.zeros(m, np.int64)
    for v in seen.values():
        np.add.at(counts, v, 1)
    return density_aware_item_layout(counts, shards)


class TestShardedServingDifferential:
    def test_user_lane_matches_single_chip(self, multichip_devices):
        X, Y, seen = _make_problem()
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        for uid in range(X.shape[0]):
            i1, s1 = single.user_topk(uid, 7)
            i2, s2 = sharded.user_topk(uid, 7)
            np.testing.assert_allclose(s1, s2, atol=1e-5)
            assert (i1 == i2).all()

    def test_users_lane_matches_single_chip(self, multichip_devices):
        X, Y, seen = _make_problem(seed=2)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        uids = np.arange(X.shape[0])
        i1, s1 = single.users_topk(uids, 9)
        i2, s2 = sharded.users_topk(uids, 9)
        fin = np.isfinite(s1)
        np.testing.assert_allclose(s1[fin], s2[fin], atol=1e-5)
        assert (i1[fin] == i2[fin]).all()

    def test_items_lane_matches_single_chip(self, multichip_devices):
        X, Y, seen = _make_problem(seed=3)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        for q in ([0], [3, 17], [1, 2, 5, 8]):
            i1, s1 = single.items_topk(q, 6)
            i2, s2 = sharded.items_topk(q, 6)
            np.testing.assert_allclose(s1, s2, atol=1e-5)
            assert (i1 == i2).all()

    def test_out_of_range_query_item_drops(self, multichip_devices):
        """An out-of-range similarity-query id DROPS from the query on
        both paths: the density-sharded store must not fault its
        inverse take, and the single store must not NaN-poison the
        whole summed query row (one bad id used to empty the result).
        """
        X, Y, seen = _make_problem(seed=16)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        m = Y.shape[0]
        for srv in (single, sharded):
            i_mixed, s_mixed = srv.items_topk([2, m + 5], 6)
            i_ref, s_ref = srv.items_topk([2], 6)
            assert (i_mixed == i_ref).all()
            np.testing.assert_allclose(s_mixed, s_ref, atol=1e-5)
            srv.items_topk([m], 3)  # all-OOB: answers, never faults

    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_precision_lanes_match(self, multichip_devices, monkeypatch,
                                   mode):
        monkeypatch.setenv("PIO_SERVE_PRECISION", mode)
        X, Y, seen = _make_problem(seed=4)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        assert sharded._mode == mode
        for uid in (0, 11, 23):
            i1, s1 = single.user_topk(uid, 6)
            i2, s2 = sharded.user_topk(uid, 6)
            np.testing.assert_allclose(s1, s2, atol=1e-4)
            assert (i1 == i2).all()

    @pytest.mark.pallas
    @pytest.mark.parametrize("mode", ["fp32", "int8"])
    def test_fused_kernel_per_shard_matches(self, multichip_devices,
                                            monkeypatch, mode):
        """The fused Pallas kernel keeps working on a sharded store:
        each shard runs it on its local tiles (interpret mode on CPU)
        and the merged result equals the single-chip XLA chain."""
        monkeypatch.setenv("PIO_SERVE_PRECISION", mode)
        X, Y, seen = _make_problem(seed=5)
        layout = _layout_from_seen(seen, Y.shape[0])
        monkeypatch.setenv("PIO_SERVE_KERNEL", "xla")
        single = DeviceTopK(X, Y, {u: v.copy() for u, v in seen.items()},
                            microbatch=False)
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        sharded = DeviceTopK(X, Y,
                             {u: v.copy() for u, v in seen.items()},
                             microbatch=False, item_layout=layout)
        assert sharded._kernel == "fused"
        for uid in (0, 9, 23):
            i1, s1 = single.user_topk(uid, 6)
            i2, s2 = sharded.user_topk(uid, 6)
            np.testing.assert_allclose(s1, s2, atol=1e-4)
            assert (i1 == i2).all()
        i1, s1 = single.items_topk([2, 7], 6)
        i2, s2 = sharded.items_topk([2, 7], 6)
        np.testing.assert_allclose(s1, s2, atol=1e-4)
        assert (i1 == i2).all()

    def test_env_shards_and_clamp(self, multichip_devices, monkeypatch):
        """PIO_SERVE_SHARDS shards a plain device store (counts derived
        from the seen sets); an impossible count clamps to the device
        plane instead of failing the deploy."""
        import jax

        X, Y, seen = _make_problem(seed=6)
        monkeypatch.setenv("PIO_SERVE_SHARDS", "4")
        srv = DeviceTopK(X, Y, seen, microbatch=False)
        assert srv.shard_count == 4
        assert srv.item_layout is not None
        monkeypatch.setenv("PIO_SERVE_SHARDS",
                           str(len(jax.devices()) * 8))
        clamped = DeviceTopK(X, Y, seen, microbatch=False)
        assert clamped.shard_count == len(jax.devices())

    def test_aot_ladder_and_zero_compile(self, multichip_devices):
        """The sharded store rides the same AOT ladder: warmup compiles
        it, steady-state dispatches hit executables (no jit fallback
        misses)."""
        X, Y, seen = _make_problem(seed=7)
        sharded = DeviceTopK(X, Y, seen, microbatch=False,
                             item_layout=_layout_from_seen(
                                 seen, Y.shape[0]))
        stats = sharded.warmup(max_k=16)
        assert stats["compiled"] > 0
        before = sharded.ladder_report()["requests"]
        sharded.user_topk(3, 10)
        sharded.users_topk(np.arange(6), 10)
        after = sharded.ladder_report()["requests"]
        assert after["hit"] - before["hit"] == 2
        assert after["missJit"] == before["missJit"]


# ---------------------------------------------------------------------------
# Sharded fold-in: patch, growth-by-resharding, item_factors view
# ---------------------------------------------------------------------------

class TestShardedFoldIn:
    def test_patch_matches_single_chip(self, multichip_devices):
        X, Y, seen = _make_problem(seed=8)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        rng = np.random.default_rng(0)
        rows = rng.normal(size=(3, X.shape[1])).astype(np.float32)
        uids = np.asarray([2, 9, 17])
        seen_upd = {int(u): np.asarray([0, 5, 6]) for u in uids}
        for srv in (single, sharded):
            srv.patch_users(uids, rows, seen_items=dict(seen_upd))
        for uid in (2, 9, 17, 0):
            i1, s1 = single.user_topk(uid, 8)
            i2, s2 = sharded.user_topk(uid, 8)
            np.testing.assert_allclose(s1, s2, atol=1e-5)
            assert (i1 == i2).all()

    def test_growth_reshards_instead_of_refusing(self, multichip_devices):
        """The PR-8 refusal is gone: unknown users grow a mesh-sharded
        store along the bucket ladder, rounded to the shard divisor,
        and the grown rows serve identically to the single-chip path."""
        X, Y, seen = _make_problem(seed=9)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        assert sharded.growable
        n = X.shape[0]
        rng = np.random.default_rng(1)
        rows = rng.normal(size=(2, X.shape[1])).astype(np.float32)
        uids = np.asarray([n + 1, n + 7])
        for srv in (single, sharded):
            srv.patch_users(uids, rows,
                            seen_items={int(u): np.asarray([1])
                                        for u in uids})
        assert sharded.user_capacity >= n + 8
        assert sharded.user_capacity % sharded.shard_count == 0
        for uid in (int(n + 1), int(n + 7)):
            i1, s1 = single.user_topk(uid, 8)
            i2, s2 = sharded.user_topk(uid, 8)
            np.testing.assert_allclose(s1, s2, atol=1e-5)
            assert (i1 == i2).all()
        # the grown sharded store still serves the OLD users unchanged
        i1, s1 = single.user_topk(0, 8)
        i2, s2 = sharded.user_topk(0, 8)
        np.testing.assert_allclose(s1, s2, atol=1e-5)

    def test_int8_growth_reshards(self, multichip_devices, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        X, Y, seen = _make_problem(seed=10)
        single, sharded = _pair(X, Y, seen,
                                _layout_from_seen(seen, Y.shape[0]))
        rng = np.random.default_rng(2)
        rows = rng.normal(size=(1, X.shape[1])).astype(np.float32)
        uid = X.shape[0] + 3
        for srv in (single, sharded):
            srv.patch_users([uid], rows,
                            seen_items={uid: np.asarray([2])})
        i1, s1 = single.user_topk(uid, 6)
        i2, s2 = sharded.user_topk(uid, 6)
        np.testing.assert_allclose(s1, s2, atol=1e-4)
        assert (i1 == i2).all()

    def test_item_factors_view_is_item_ordered(self, multichip_devices):
        """``item_factors`` (the fold-in solve's fixed side) must hand
        back ITEM-id order whatever the store's shard permutation —
        fold_in_users indexes it by item id."""
        X, Y, seen = _make_problem(seed=11)
        sharded = DeviceTopK(X, Y, seen, microbatch=False,
                             item_layout=_layout_from_seen(
                                 seen, Y.shape[0]))
        np.testing.assert_allclose(np.asarray(sharded.item_factors),
                                   Y, atol=1e-6)

    def test_fold_solve_differential_on_sharded_store(
            self, multichip_devices):
        """fold_in_users against a density-sharded store's item view ==
        against the raw host factors (the fold-in-patched-rows gate)."""
        from predictionio_tpu.ops.als import ALSParams, fold_in_users

        X, Y, seen = _make_problem(seed=12)
        sharded = DeviceTopK(X, Y, seen, microbatch=False,
                             item_layout=_layout_from_seen(
                                 seen, Y.shape[0]))
        params = ALSParams(rank=X.shape[1], num_iterations=1, seed=0)
        cols = [np.asarray([1, 4, 9]), np.asarray([2, 30])]
        vals = [np.asarray([5.0, 3.0, 4.0], np.float32),
                np.asarray([4.0, 5.0], np.float32)]
        ref = fold_in_users(Y, cols, vals, params)
        got = fold_in_users(sharded.item_factors, cols, vals, params)
        np.testing.assert_allclose(got, ref, atol=1e-5)


# ---------------------------------------------------------------------------
# Per-shard HBM report (satellite: the aggregate hides a hot shard)
# ---------------------------------------------------------------------------

class TestShardMemoryReport:
    def test_per_shard_breakdown(self, multichip_devices):
        X, Y, seen = _make_problem(seed=13)
        layout = _layout_from_seen(seen, Y.shape[0])
        sharded = DeviceTopK(X, Y, seen, microbatch=False,
                             item_layout=layout)
        rep = sharded.memory_report()
        assert rep["nShards"] == 4
        shards = rep["shards"]
        assert len(shards) == 4
        assert sum(e["items"] for e in shards) == Y.shape[0]
        assert all(e["factorBytes"] > 0 for e in shards)
        total_mass = sum(e["interactions"] for e in shards)
        assert total_mass == sum(len(v) for v in seen.values())
        assert rep["shardBalance"]["nShards"] == 4

    def test_single_store_has_no_shard_block(self):
        X, Y, seen = _make_problem(seed=14)
        srv = DeviceTopK(X, Y, seen, microbatch=False)
        rep = srv.memory_report()
        assert "shards" not in rep

    def test_pio_top_renders_shard_lines(self, multichip_devices):
        from predictionio_tpu.tools.top_command import render

        X, Y, seen = _make_problem(seed=15)
        sharded = DeviceTopK(X, Y, seen, microbatch=False,
                             item_layout=_layout_from_seen(
                                 seen, Y.shape[0]))
        stats = {"device": {"stores": [
            {"store": sharded.memory_report(), "aotLadder":
             sharded.ladder_report()}]}}
        text = render(stats, {})
        assert "shard    #0" in text
        assert "interactions" in text


# ---------------------------------------------------------------------------
# Sharded training factors differential (tentpole gate 1)
# ---------------------------------------------------------------------------

class TestShardedTrainingDifferential:
    def test_device_trained_factors_match_single_chip(
            self, multichip_mesh):
        from predictionio_tpu.ops.als import (
            ALSParams,
            bucket_ratings_pair,
            train_als_bucketed,
        )
        from predictionio_tpu.parallel.als_sharding import (
            train_als_device,
        )

        rng = np.random.default_rng(3)
        rows = rng.integers(0, 30, 400)
        cols = rng.integers(0, 50, 400)
        vals = rng.integers(1, 6, 400).astype(np.float32)
        us, its = bucket_ratings_pair(rows, cols, vals, 30, 50)
        params = ALSParams(rank=8, num_iterations=3, seed=1)
        Xd, Yd = train_als_device(us, its, params, mesh=multichip_mesh)
        Xh, Yh = train_als_bucketed(us, its, params)
        np.testing.assert_allclose(np.asarray(Xd)[:30], Xh, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(Yd)[:50], Yh, rtol=1e-4,
                                   atol=1e-4)

    def test_sharded_model_serves_with_density_layout(
            self, multichip_devices, mem_storage):
        """The PAlgorithm template attaches the density layout to its
        model on a multi-device runtime, and serving through it matches
        the host reference."""
        from predictionio_tpu.controller import (
            ComputeContext,
            EngineParams,
        )
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates.recommendation import (
            DataSourceParams,
        )
        from predictionio_tpu.templates.recommendation.engine import (
            Query,
            sharded_engine_factory,
        )

        import datetime as _dt

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data import storage as storage_mod
        from predictionio_tpu.data.storage.base import App

        aid = storage_mod.get_metadata_apps().insert(App(0, "shrd"))
        le = storage_mod.get_levents()
        le.init(aid)
        rng = np.random.default_rng(5)
        t0 = _dt.datetime(2024, 1, 1, tzinfo=UTC)
        le.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{int(i)}",
                  properties={"rating": float(rng.integers(3, 6))},
                  event_time=t0)
            for u in range(16)
            for i in rng.choice(12, size=5, replace=False)], aid)
        engine = sharded_engine_factory()
        params = EngineParams(
            data_source_params=("", DataSourceParams(app_name="shrd")),
            algorithm_params_list=[
                ("als", ALSParams(rank=8, num_iterations=2, seed=2))])
        ctx = ComputeContext()
        td = engine.data_source_class_map[""](
            params.data_source_params[1]).read_training(ctx)
        pd = engine.preparator_class_map[""](None).prepare(ctx, td)
        algo = engine.algorithm_class_map["als"](
            params.algorithm_params_list[0][1])
        model = algo.train(ctx, pd)
        assert model.item_layout is not None
        srv = model.device_server()
        assert srv.shard_count > 1
        res = algo.predict(model, Query(user="u1", num=5))
        assert res.item_scores
        # every recommended item decodes to a REAL item id (the
        # permutation translated back correctly)
        for s in res.item_scores:
            assert s.item in model.item_map


# ---------------------------------------------------------------------------
# Deployed fold-in freshness against a sharded store (tentpole gate 3)
# ---------------------------------------------------------------------------

def _post(addr, path, body):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode("utf-8"))
    conn.close()
    return resp.status, data


@pytest.mark.online
class TestShardedDeployedFoldIn:
    def test_new_user_servable_on_sharded_deploy(self, mem_storage,
                                                 monkeypatch,
                                                 multichip_devices):
        """The fold-in freshness path against a sharded deploy: the
        store density-shards over 4 devices at deploy, the consumer
        starts (no more growable refusal), and a brand-new user's
        events become servable without /reload — growing the sharded
        store through the resharding path."""
        from predictionio_tpu.data import storage as storage_mod
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.controller import (
            ComputeContext,
            EngineParams,
        )
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates.recommendation import (
            DataSourceParams,
            engine_factory,
        )
        from predictionio_tpu.workflow import (
            QueryServer,
            ServerConfig,
            run_train,
        )
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig,
            new_engine_instance,
        )

        monkeypatch.setenv("PIO_FOLDIN", "1")
        monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
        monkeypatch.setenv("PIO_SERVE_SHARDS", "4")

        aid = storage_mod.get_metadata_apps().insert(App(0, "shfold"))
        le = storage_mod.get_levents()
        le.init(aid)
        rng = np.random.default_rng(7)
        t0 = dt.datetime(2024, 1, 1, tzinfo=UTC)

        def rate(u, i, at):
            return Event(event="rate", entity_type="user", entity_id=u,
                         target_entity_type="item", target_entity_id=i,
                         properties={"rating": 5.0},
                         event_time=t0 + dt.timedelta(seconds=at))

        le.insert_batch(
            [rate(f"u{u}", f"i{int(i)}", u)
             for u in range(16)
             for i in rng.choice(12, size=5, replace=False)], aid)
        engine = engine_factory()
        params = EngineParams(
            data_source_params=("", DataSourceParams(
                app_name="shfold")),
            algorithm_params_list=[
                ("als", ALSParams(rank=8, num_iterations=2, seed=3))])
        cfg = WorkflowConfig(
            engine_factory="predictionio_tpu.templates."
                           "recommendation:engine_factory")
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=ComputeContext())
        assert iid is not None
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            model = srv._deployment.models[0]
            store = model.device_server()
            assert store.shard_count == 4
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "fresh9"})
            assert status == 200 and result["itemScores"] == []
            le.insert_batch([rate("fresh9", f"i{i}", 1000 + i)
                             for i in range(3)], aid)
            deadline = time.time() + 20
            while time.time() < deadline:
                status, result = _post(srv.address, "/queries.json",
                                       {"user": "fresh9", "num": 5})
                assert status == 200
                if result.get("itemScores"):
                    break
                time.sleep(0.05)
            assert result.get("itemScores"), \
                "new user never became servable on the sharded deploy"
            items = {s["item"] for s in result["itemScores"]}
            assert items.isdisjoint({"i0", "i1", "i2"})
            # the store is still sharded after the growth patch
            assert store.shard_count == 4
            assert store.user_capacity % 4 == 0
        finally:
            srv.stop()
