"""Device-resident serving tests: DeviceTopK vs host oracle, the
PAlgorithm sharded-model flavor end to end, and serving through the
query server from a model whose factors never left HBM (SURVEY hard
parts #4/#5; PAlgorithm.scala:44-126)."""

import datetime as dt
import http.client
import json

import numpy as np
import pytest

from predictionio_tpu.controller import ComputeContext, EngineParams
from predictionio_tpu.data import storage
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    train_als_bucketed,
)
from predictionio_tpu.ops.serving import DeviceTopK, seen_bitmap

UTC = dt.timezone.utc
CTX = ComputeContext()


def host_oracle_topk(X, Y, seen, uid, k, n_items=None):
    scores = Y @ X[uid]
    if n_items is not None:
        scores = scores[:n_items]
    s = seen.get(uid)
    if s is not None and len(s):
        scores = scores.copy()
        scores[s] = -np.inf
    order = np.argsort(-scores)[:k]
    keep = np.isfinite(scores[order])
    return order[keep], scores[order][keep]


class TestDeviceTopK:
    @pytest.fixture(scope="class")
    def factors(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(20, 6)).astype(np.float32)
        Y = rng.normal(size=(33, 6)).astype(np.float32)
        seen = {u: rng.choice(33, size=rng.integers(1, 6), replace=False)
                for u in range(0, 20, 2)}
        return X, Y, seen

    def test_user_topk_matches_host_oracle(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        for uid in (0, 1, 7, 19):
            idx, scores = srv.user_topk(uid, 5)
            oidx, oscores = host_oracle_topk(X, Y, seen, uid, 5)
            np.testing.assert_allclose(scores, oscores, rtol=1e-5)
            assert set(idx.tolist()) == set(oidx.tolist())

    def test_seen_items_masked_on_device(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        idx, _ = srv.user_topk(0, 33)
        assert not (set(idx.tolist()) & set(seen[0].tolist()))

    def test_padded_rows_never_served(self, factors):
        X, Y, seen = factors
        # pretend rows were padded: true n_items is 30, rows 30..32 junk
        srv = DeviceTopK(X, Y, seen, n_items=30)
        idx, _ = srv.user_topk(1, 33)
        assert idx.max() < 30

    def test_items_topk_masks_query_items(self, factors):
        X, Y, _ = factors
        srv = DeviceTopK(X, Y)
        idx, scores = srv.items_topk([2, 5], 6)
        assert 2 not in idx and 5 not in idx
        assert len(idx) == 6
        # descending
        assert all(a >= b for a, b in zip(scores, scores[1:]))

    def test_bucket_reuse(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        # micro-batched path: all single queries ride the batched
        # program at the same (k-bucket, uid-bucket)
        srv.user_topk(0, 3)
        srv.user_topk(1, 9)     # same 16-bucket
        srv.user_topk(2, 16)
        assert len(srv._batch_programs) == 1
        srv.user_topk(0, 17)    # 32-bucket -> clipped to n_items=33
        assert len(srv._batch_programs) == 2
        # the direct (unbatched) program path buckets identically
        srv._user_topk_direct(0, 3)
        srv._user_topk_direct(1, 9)
        assert len(srv._user_programs) == 1

    def test_sharded_factors_serve_without_host_gather(self):
        """Factors sharded over an 8-device mesh serve directly."""
        import jax

        from predictionio_tpu.parallel.als_sharding import train_als_device
        from predictionio_tpu.parallel.distributed import host_aware_mesh

        rng = np.random.default_rng(0)
        n_u, n_i, nnz = 24, 16, 150
        rows = rng.integers(0, n_u, nnz)
        cols = rng.integers(0, n_i, nnz)
        vals = rng.random(nnz).astype(np.float32) + 0.5
        us, its = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        params = ALSParams(rank=4, num_iterations=2, seed=1)

        mesh = host_aware_mesh(model=2)
        Xd, Yd = train_als_device(us, its, params, mesh=mesh)
        assert hasattr(Xd, "sharding") and Xd.sharding.mesh.size == \
            len(jax.devices())
        # padded to the mesh divisor, still sharded (never gathered)
        assert Xd.shape[0] >= n_u and Yd.shape[0] >= n_i

        srv = DeviceTopK(Xd, Yd, None, n_users=n_u, n_items=n_i)
        idx, scores = srv.user_topk(3, 5)

        # oracle: the same training gathered to host
        X, Y = train_als_bucketed(us, its, params)
        oidx, oscores = host_oracle_topk(X, Y, {}, 3, 5)
        np.testing.assert_allclose(scores, oscores[:len(scores)], rtol=1e-4)
        assert set(idx.tolist()) <= set(oidx.tolist())

    def test_users_topk_matches_single_query_path(self, factors):
        """The batched program (one dispatch, one packed fetch) returns
        exactly what N single-query dispatches would."""
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        uids = np.asarray([0, 3, 7, 12, 19])
        idx_b, scores_b = srv.users_topk(uids, 5)
        assert idx_b.shape == (5, 5) and scores_b.shape == (5, 5)
        for row, uid in enumerate(uids):
            idx1, scores1 = srv.user_topk(int(uid), 5)
            valid = np.isfinite(scores_b[row])
            np.testing.assert_allclose(scores_b[row][valid], scores1,
                                       rtol=1e-5)
            assert idx_b[row][valid].tolist() == idx1.tolist()

    def test_users_topk_bucket_reuse(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        srv.users_topk([0, 1, 2], 5)       # uid bucket 8, k bucket 16
        srv.users_topk(np.arange(7), 10)   # same buckets
        assert len(srv._batch_programs) == 1
        srv.users_topk(np.arange(9), 5)    # uid bucket 16
        assert len(srv._batch_programs) == 2

    def test_packed_output_is_integer_and_exact(self):
        """One fetch carries scores AND indices. The carrier must be an
        integer buffer: reinterpreted as float32, indices below 2^23
        are denormals, which the TPU flushes to zero (on the v5e a
        float32 carrier returned every index as 0). The round trip is
        exact for any score bit pattern, -inf and denormals included."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.serving import _pack, _unpack

        scores = np.asarray([[3.5, 1e-42, -0.0, -np.inf],
                             [np.inf, 2.0, 1.0, 0.5]], dtype=np.float32)
        idx = np.asarray([[0, 1, 8388607, 26999],
                          [2**31 - 1, 5, 6, 7]], dtype=np.int32)
        packed = _pack(jnp.asarray(scores), jnp.asarray(idx))
        assert packed.dtype == jnp.int32 and packed.shape == (2, 8)
        got_idx, got_scores = _unpack(np.asarray(packed), 4)
        np.testing.assert_array_equal(got_idx, idx)
        assert got_scores.dtype == np.float32
        np.testing.assert_array_equal(got_scores.view(np.int32),
                                      scores.view(np.int32))

    def test_seen_bitmap_packing(self):
        # 70 positions -> 3 words of bits in a row of one lane tile
        # (128 words, seen_row_words); bit j of word w = position
        # 32*w + j, bit 31 included (the int32 sign bit); out-of-range
        # ids drop, so the padding words stay zero
        bits = seen_bitmap({0: np.asarray([3, 1]),
                            2: np.asarray([7, 31, 69, 70, -1])}, 4, 70)
        assert bits.shape == (4, 128) and bits.dtype == np.int32
        u = bits.view(np.uint32)
        assert u[0, :3].tolist() == [(1 << 3) | (1 << 1), 0, 0]
        assert not u[1].any() and not u[3].any()
        assert u[2, :3].tolist() == [(1 << 7) | (1 << 31), 0, 1 << 5]
        assert not u[:, 3:].any()


class TestMicroBatching:
    """Concurrent single-query callers share device dispatches
    (round-4 verdict weak #5); per-query results stay exact."""

    @pytest.fixture(scope="class")
    def factors(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(20, 6)).astype(np.float32)
        Y = rng.normal(size=(33, 6)).astype(np.float32)
        seen = {u: rng.choice(33, size=rng.integers(1, 6), replace=False)
                for u in range(0, 20, 2)}
        return X, Y, seen

    def test_concurrent_queries_correct_and_grouped(self, factors):
        import threading
        import time

        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        # slow the batched program so in-flight time accumulates real
        # groups (on CPU a dispatch is too fast to overlap otherwise)
        orig = srv.users_topk

        def slow_users_topk(uids, k):
            time.sleep(0.02)
            return orig(uids, k)

        srv.users_topk = slow_users_topk
        results = {}
        errors = []

        def worker(tx):
            try:
                for i in range(6):
                    uid = (tx * 6 + i) % X.shape[0]
                    k = 3 + (i % 3)
                    results[(tx, i)] = (uid, k, srv.user_topk(uid, k))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors
        total = 8 * 6
        assert len(results) == total
        # grouping happened: far fewer dispatches than queries, and
        # wall-clock far under the serial 48 x 20ms
        assert srv._batcher.dispatches < total * 0.75
        assert srv._batcher.batched_queries == total
        assert wall < total * 0.02 * 0.75
        for (tx, i), (uid, k, (idx, scores)) in results.items():
            want_idx, want_scores = host_oracle_topk(X, Y, seen, uid, k)
            assert idx.tolist() == want_idx.tolist(), (uid, k)
            np.testing.assert_allclose(scores, want_scores, rtol=1e-5)

    def test_mixed_k_in_one_group(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        # a generous batching window lets all five queries join ONE
        # EDF batch despite arriving sequentially
        b = srv._batcher
        d0 = b.dispatches
        futs = {(u, k): b.submit_async(u, k, window=0.5)
                for u, k in [(0, 2), (1, 7), (2, 4), (3, 1), (4, 5)]}
        for (u, k), fut in futs.items():
            res, row = fut.result(timeout=10)
            idx, scores = res.render(row, k)
            want_idx, _ = host_oracle_topk(X, Y, seen, u, k)
            assert idx.tolist() == want_idx.tolist()
        assert b.dispatches == d0 + 1  # one shared dispatch
        assert b.stats()["dispatchTriggers"]["window"] >= 1

    def test_error_propagates_to_all_waiters(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)

        def boom(uids, k):
            raise RuntimeError("device fell over")

        srv.users_topk = boom
        with pytest.raises(RuntimeError, match="fell over"):
            srv.user_topk(0, 3)

    def test_disable_flag(self, factors, monkeypatch):
        X, Y, seen = factors
        monkeypatch.setenv("PIO_SERVING_MICROBATCH", "OFF")  # any case
        srv = DeviceTopK(X, Y, seen)
        assert srv._batcher is None
        idx, _ = srv.user_topk(1, 4)
        want_idx, _ = host_oracle_topk(X, Y, seen, 1, 4)
        assert idx.tolist() == want_idx.tolist()

    def test_large_group_uses_warmed_bucket(self, factors):
        """A group larger than 8 pads to its power-of-two uid bucket —
        which the AOT ladder precompiled, so live traffic never compiles
        a new batch program."""
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        srv.warmup(max_k=16)
        compiled = set(srv._batch_programs)  # jit fallbacks, if any
        b = srv._batcher
        d0 = b.dispatches
        futs = [b.submit_async(u % X.shape[0], 3, window=0.5)
                for u in range(21)]
        for fut in futs:
            res, row = fut.result(timeout=10)
            assert res.render(row, 3)[0] is not None
        assert b.dispatches == d0 + 1  # the 21 queries shared one batch
        # no NEW jit batch program was compiled by the 21-query group
        # (bucket 32 came from the AOT ladder)
        assert set(srv._batch_programs) == compiled

    def test_item_queries_batched_and_correct(self, factors):
        import threading
        import time

        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        oracle = DeviceTopK(X, Y, seen, microbatch=False)
        orig = srv._items_topk_batched

        def slow_batched(idxs, masks, k):
            time.sleep(0.02)
            return orig(idxs, masks, k)

        srv._items_topk_batched = slow_batched
        results = {}
        errors = []

        def worker(tx):
            try:
                for i in range(4):
                    items = [int(x) for x in
                             {(tx + i) % 33, (tx * 3 + i) % 33}]
                    k = 3 + (i % 2)
                    results[(tx, i)] = (items, k,
                                        srv.items_topk(items, k))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        b = srv._item_batcher
        assert b.batched_queries == 24
        assert b.dispatches < 24
        for (tx, i), (items, k, (idx, scores)) in results.items():
            want_idx, want_scores = oracle.items_topk(items, k)
            assert idx.tolist() == want_idx.tolist(), (items, k)
            np.testing.assert_allclose(scores, want_scores, rtol=1e-5)

    def test_item_warmup_covers_batcher_buckets(self, factors):
        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        srv.warmup(max_k=16)
        compiled = set(srv._item_programs)
        # a 13-query group (row bucket 16, from the AOT ladder) hits
        # warmed programs only
        b = srv._item_batcher
        d0 = b.dispatches
        futs = [b.submit_async((u % 33,), 3, window=0.5)
                for u in range(13)]
        for fut in futs:
            res, row = fut.result(timeout=10)
            assert res.render(row, 3)[0] is not None
        assert b.dispatches == d0 + 1
        assert set(srv._item_programs) == compiled

    def test_close_stops_dispatcher_and_gc_releases(self, factors):
        import gc
        import threading
        import time
        import weakref

        X, Y, seen = factors
        srv = DeviceTopK(X, Y, seen)
        srv.user_topk(0, 3)  # starts the dispatcher
        assert any(t.name == "pio-microbatch-dispatcher" for t in
                   threading.enumerate())
        srv.close()
        time.sleep(0.1)
        with pytest.raises(RuntimeError, match="closed"):
            srv.user_topk(0, 3)
        # GC path: a dropped server's dispatcher exits on its own
        srv2 = DeviceTopK(X, Y, seen)
        srv2.user_topk(0, 3)
        ref = weakref.ref(srv2)
        del srv2
        gc.collect()
        for _ in range(30):
            if ref() is None:
                break
            time.sleep(0.1)
        assert ref() is None  # the thread does not pin the factors


class TestHostTopK:
    """HostTopK must be observably interchangeable with DeviceTopK —
    `choose_server` swaps them by model size/placement."""

    @pytest.fixture(scope="class")
    def factors(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(20, 6)).astype(np.float32)
        Y = rng.normal(size=(33, 6)).astype(np.float32)
        seen = {u: rng.choice(33, size=rng.integers(1, 6), replace=False)
                for u in range(0, 20, 2)}
        return X, Y, seen

    def test_matches_device_server(self, factors):
        from predictionio_tpu.ops.serving import HostTopK

        X, Y, seen = factors
        hsrv, dsrv = HostTopK(X, Y, seen), DeviceTopK(X, Y, seen)
        for uid in (0, 1, 7, 19):
            hi, hs = hsrv.user_topk(uid, 5)
            di, ds = dsrv.user_topk(uid, 5)
            np.testing.assert_allclose(hs, ds, rtol=1e-5)
            assert set(hi.tolist()) == set(di.tolist())
        hi, hs = hsrv.items_topk([2, 5], 6)
        di, ds = dsrv.items_topk([2, 5], 6)
        np.testing.assert_allclose(np.sort(hs)[::-1], np.sort(ds)[::-1],
                                   rtol=1e-4)
        assert set(hi.tolist()) == set(di.tolist())

    def test_users_topk_batch(self, factors):
        from predictionio_tpu.ops.serving import HostTopK

        X, Y, seen = factors
        hsrv = HostTopK(X, Y, seen)
        idx, scores = hsrv.users_topk([0, 3, 19], 5)
        assert idx.shape == (3, 5)
        for row, uid in enumerate((0, 3, 19)):
            i1, s1 = hsrv.user_topk(uid, 5)
            valid = np.isfinite(scores[row])
            assert idx[row][valid].tolist() == i1.tolist()

    def test_padded_rows_never_served(self, factors):
        from predictionio_tpu.ops.serving import HostTopK

        X, Y, seen = factors
        idx, _ = HostTopK(X, Y, seen, n_items=30).user_topk(1, 33)
        assert idx.max() < 30

    def test_choose_server_policy(self, factors, monkeypatch):
        from predictionio_tpu.ops.serving import (
            HostTopK, choose_server,
        )

        X, Y, seen = factors
        # auto: small host factors -> host backend
        assert isinstance(choose_server(X, Y, seen), HostTopK)
        # forced device
        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        assert isinstance(choose_server(X, Y, seen), DeviceTopK)
        # sharded/device factors always device even on auto
        import jax.numpy as jnp

        monkeypatch.setenv("PIO_SERVING_BACKEND", "auto")
        srv = choose_server(jnp.asarray(X), jnp.asarray(Y), seen)
        assert isinstance(srv, DeviceTopK)
        # host backend refuses device-resident factors
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        with pytest.raises(ValueError):
            choose_server(jnp.asarray(X), jnp.asarray(Y), seen)


class TestServePrecision:
    """PIO_SERVE_PRECISION=bf16 opt-in: bfloat16 factor store in HBM,
    fp32 score accumulation, gated on top-k agreement with the fp32
    server (the serving arm of the ops/als.py precision policy)."""

    @pytest.fixture()
    def separated(self):
        """Factors whose score gaps (>= 1.0 between item ranks, score
        magnitudes <= ~40) dwarf bf16 rounding (~0.15 at that scale):
        the bf16 server must return the identical top-k ordering."""
        rng = np.random.default_rng(11)
        n_users, n_items, rank = 12, 40, 8
        X = np.zeros((n_users, rank), dtype=np.float32)
        X[:, 0] = 1.0
        X[:, 1] = rng.uniform(-0.01, 0.01, size=n_users)
        Y = rng.uniform(-0.01, 0.01, size=(n_items, rank)) \
            .astype(np.float32)
        # item i scores ~ i + noise<<1 for every user, in every user's
        # ranking — well separated at any k
        Y[:, 0] = np.arange(n_items, dtype=np.float32)
        return X, Y

    def test_unknown_value_raises(self, monkeypatch):
        from predictionio_tpu.ops.serving import _serve_precision_mode

        monkeypatch.setenv("PIO_SERVE_PRECISION", "fp8")
        with pytest.raises(ValueError, match="PIO_SERVE_PRECISION"):
            _serve_precision_mode()

    def test_bf16_store_and_fp32_scores(self, separated, monkeypatch):
        X, Y = separated
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        srv = DeviceTopK(X, Y)
        assert srv._X.dtype == np.dtype("bfloat16").newbyteorder("=") \
            or str(srv._X.dtype) == "bfloat16"
        idx, scores = srv.user_topk(0, 10)
        assert scores.dtype == np.float32

    def test_topk_overlap_with_fp32_server(self, separated, monkeypatch):
        X, Y = separated
        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        ref = DeviceTopK(X, Y)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        srv = DeviceTopK(X, Y)
        for uid in range(X.shape[0]):
            ri, rs = ref.user_topk(uid, 10)
            bi, bs = srv.user_topk(uid, 10)
            assert ri.tolist() == bi.tolist()
            np.testing.assert_allclose(bs, rs, rtol=0.02, atol=0.2)
        # batched path agrees too
        ri, _ = ref.users_topk(np.arange(8), 10)
        bi, _ = srv.users_topk(np.arange(8), 10)
        np.testing.assert_array_equal(ri, bi)

    def test_items_topk_overlap(self, separated, monkeypatch):
        X, _ = separated
        # planar items at designed angles: the two query items sit at
        # m +- 0.3 rad, every candidate at m + 0.13*(i-1) — summed
        # cosine is 2*cos(0.3)*cos(angle - m), so ranking follows the
        # angular offsets with score gaps >= ~0.02, an order of
        # magnitude above bf16 rounding of unit vectors
        m = 0.8
        phi = np.array([m - 0.3, m + 0.3]
                       + [m + 0.13 * i for i in range(1, 23)])
        Y = np.zeros((24, 8), dtype=np.float32)
        Y[:, 0] = np.cos(phi)
        Y[:, 1] = np.sin(phi)
        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        ref = DeviceTopK(X, Y)
        ri, _ = ref.items_topk([0, 1], 5)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        srv = DeviceTopK(X, Y)
        bi, bs = srv.items_topk([0, 1], 5)
        assert ri.tolist() == bi.tolist()
        assert np.isfinite(bs).all()

    def test_choose_server_forces_device_backend(self, monkeypatch):
        from predictionio_tpu.ops.serving import choose_server

        rng = np.random.default_rng(0)
        X = rng.normal(size=(10, 4)).astype(np.float32)
        Y = rng.normal(size=(12, 4)).astype(np.float32)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        monkeypatch.delenv("PIO_SERVING_BACKEND", raising=False)
        # auto would pick HostTopK at this size; bf16 is an HBM policy
        assert isinstance(choose_server(X, Y), DeviceTopK)
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        with pytest.raises(ValueError, match="PIO_SERVE_PRECISION"):
            choose_server(X, Y)

    def test_host_server_accepts_bf16_factors(self, monkeypatch):
        """Gathered bf16 models (ml_dtypes numpy) still serve on host:
        HostTopK casts to fp32 (numpy has no bf16 BLAS)."""
        import ml_dtypes

        from predictionio_tpu.ops.serving import HostTopK

        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(10, 4)).astype(ml_dtypes.bfloat16)
        Y = rng.normal(size=(12, 4)).astype(ml_dtypes.bfloat16)
        srv = HostTopK(X, Y)
        idx, scores = srv.user_topk(0, 5)
        assert len(idx) == 5 and np.isfinite(scores).all()


class TestInt8Serving:
    """PIO_SERVE_PRECISION=int8: int8 factor store with per-row fp32
    absmax scales, fp32 score accumulation — the serving arm one stop
    further down the Tensor Casting axis than bf16, same gates."""

    @pytest.fixture()
    def separated(self):
        """Score gaps (>= ~1.0 between ranks at magnitudes <= ~40)
        dwarf the int8 step of these rows (scale ~ 40/127 -> error
        <= ~0.16 per entry): identical top-k ordering required."""
        rng = np.random.default_rng(11)
        n_users, n_items, rank = 12, 40, 8
        X = np.zeros((n_users, rank), dtype=np.float32)
        X[:, 0] = 1.0
        X[:, 1] = rng.uniform(-0.01, 0.01, size=n_users)
        Y = rng.uniform(-0.01, 0.01, size=(n_items, rank)) \
            .astype(np.float32)
        Y[:, 0] = np.arange(n_items, dtype=np.float32)
        return X, Y

    def test_int8_store_and_fp32_scores(self, separated, monkeypatch):
        from predictionio_tpu.ops.quantize import is_quantized

        X, Y = separated
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        srv = DeviceTopK(X, Y)
        assert srv._mode == "int8"
        assert is_quantized(srv._X) and is_quantized(srv._Y)
        assert str(srv._X.data.dtype) == "int8"
        assert str(srv._X.scale.dtype) == "float32"
        idx, scores = srv.user_topk(0, 10)
        assert scores.dtype == np.float32

    def test_topk_overlap_with_fp32_server(self, separated, monkeypatch):
        X, Y = separated
        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        ref = DeviceTopK(X, Y)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        srv = DeviceTopK(X, Y)
        for uid in range(X.shape[0]):
            ri, rs = ref.user_topk(uid, 10)
            qi, qs = srv.user_topk(uid, 10)
            assert ri.tolist() == qi.tolist()
            np.testing.assert_allclose(qs, rs, rtol=0.05, atol=0.5)
        ri, _ = ref.users_topk(np.arange(8), 10)
        qi, _ = srv.users_topk(np.arange(8), 10)
        np.testing.assert_array_equal(ri, qi)

    def test_bf16_store_requantizes_to_int8(self, separated,
                                            monkeypatch):
        """A bf16-trained store re-quantizes (through fp32) when served
        int8 — same ordering on separated factors."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import is_quantized

        X, Y = separated
        Xb = jnp.asarray(X).astype(jnp.bfloat16)
        Yb = jnp.asarray(Y).astype(jnp.bfloat16)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        srv = DeviceTopK(Xb, Yb)
        assert is_quantized(srv._Y)
        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        ref = DeviceTopK(X, Y)
        ri, _ = ref.user_topk(2, 8)
        qi, _ = srv.user_topk(2, 8)
        assert ri.tolist() == qi.tolist()

    def test_quantized_input_forces_int8_mode(self, separated,
                                              monkeypatch):
        """Passing an int8+scales store directly (a quantized artifact)
        serves int8 regardless of the env."""
        from predictionio_tpu.ops.quantize import quantize_rows_int8_np

        X, Y = separated
        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        srv = DeviceTopK(quantize_rows_int8_np(X),
                         quantize_rows_int8_np(Y))
        assert srv._mode == "int8"
        idx, scores = srv.user_topk(0, 5)
        assert np.isfinite(scores).all()

    def test_item_factors_dequantized_for_foldin(self, separated,
                                                 monkeypatch):
        """The fold-in solve reads a dense fp32 item view (the training
        lane has no int8 side), within the quantization error bound of
        the source factors."""
        X, Y = separated
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        srv = DeviceTopK(X, Y)
        Yd = np.asarray(srv.item_factors)
        assert Yd.dtype == np.float32
        step = np.abs(Y).max(axis=1, keepdims=True) / 127.0
        assert (np.abs(Yd[:Y.shape[0]] - Y) <= step / 2 + 1e-7).all()

    def test_choose_server_forces_device_backend(self, monkeypatch):
        from predictionio_tpu.ops.serving import choose_server

        rng = np.random.default_rng(0)
        X = rng.normal(size=(10, 4)).astype(np.float32)
        Y = rng.normal(size=(12, 4)).astype(np.float32)
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        monkeypatch.delenv("PIO_SERVING_BACKEND", raising=False)
        # auto would pick HostTopK at this size; int8 is an HBM policy
        assert isinstance(choose_server(X, Y), DeviceTopK)
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        with pytest.raises(ValueError, match="PIO_SERVE_PRECISION"):
            choose_server(X, Y)

    def test_host_server_accepts_int8_store(self, monkeypatch):
        from predictionio_tpu.ops.quantize import quantize_rows_int8_np
        from predictionio_tpu.ops.serving import HostTopK

        monkeypatch.delenv("PIO_SERVE_PRECISION", raising=False)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(10, 4)).astype(np.float32)
        Y = rng.normal(size=(12, 4)).astype(np.float32)
        srv = HostTopK(quantize_rows_int8_np(X),
                       quantize_rows_int8_np(Y))
        assert srv._X.dtype == np.float32
        idx, scores = srv.user_topk(0, 5)
        assert len(idx) == 5 and np.isfinite(scores).all()

    def test_seen_masking_still_applies(self, separated, monkeypatch):
        X, Y = separated
        seen = {0: np.asarray([39, 38, 37])}
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        srv = DeviceTopK(X, Y, seen)
        idx, _ = srv.user_topk(0, 10)
        assert not (set(idx.tolist()) & {39, 38, 37})


class TestScoreEinsumExplicitMode:
    """_score_einsum takes the store's declared precision explicitly —
    operand-dtype sniffing is gone, so a mixed-dtype operand pair can
    no longer silently steer the accumulate path (ISSUE-11 satellite
    regression)."""

    def _operands(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(2)
        Y = jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))
        u = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
        return Y, u

    def test_mode_is_required(self):
        from predictionio_tpu.ops.serving import _score_einsum

        Y, u = self._operands()
        with pytest.raises(TypeError):
            _score_einsum("mr,r->m", Y, u)

    def test_unknown_mode_raises(self):
        from predictionio_tpu.ops.serving import _score_einsum

        Y, u = self._operands()
        with pytest.raises(ValueError, match="unknown serving"):
            _score_einsum("mr,r->m", Y, u, mode="fp16")

    def test_mixed_dtypes_follow_declared_mode(self):
        """A bf16 operand under mode='fp32' accumulates fp32 on the
        HIGHEST path (result == fp32 computation of the cast operands)
        — the old sniffer would have taken the bf16 branch because ONE
        operand was bf16."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.serving import _score_einsum

        Y, u = self._operands()
        Yb = Y.astype(jnp.bfloat16)
        got = _score_einsum("mr,r->m", Yb, u, mode="fp32")
        assert got.dtype == jnp.float32
        want = _score_einsum("mr,r->m", Yb.astype(jnp.float32), u,
                             mode="fp32")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_all_modes_return_fp32(self):
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import quantize_rows_int8
        from predictionio_tpu.ops.serving import _score_einsum

        Y, u = self._operands()
        assert _score_einsum("mr,r->m", Y, u,
                             mode="fp32").dtype == jnp.float32
        assert _score_einsum("mr,r->m", Y.astype(jnp.bfloat16),
                             u.astype(jnp.bfloat16),
                             mode="bf16").dtype == jnp.float32
        got = _score_einsum("mr,r->m", quantize_rows_int8(Y), u,
                            mode="int8")
        assert got.dtype == jnp.float32

    def test_int8_mode_dequantizes_per_row(self):
        """int8 scoring == dequantize-then-fp32-einsum, bitwise."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import (
            dequantize_rows_np,
            quantize_rows_int8,
        )
        from predictionio_tpu.ops.serving import _score_einsum

        Y, u = self._operands()
        Yq = quantize_rows_int8(Y)
        got = np.asarray(_score_einsum("mr,r->m", Yq, u, mode="int8"))
        want = dequantize_rows_np(Yq) @ np.asarray(u)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def _seed(app_name="recapp"):
    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    t0 = dt.datetime(2021, 1, 1, tzinfo=UTC)
    events = []
    for u in range(20):
        group = "a" if u < 10 else "b"
        for _ in range(8):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"{group}{rng.integers(0, 10)}",
                properties={"rating": float(rng.integers(4, 6))},
                event_time=t0))
    le.insert_batch(events, aid)
    return aid


SHARDED_FACTORY = ("predictionio_tpu.templates.recommendation"
                   ":sharded_engine_factory")


def _engine_params():
    from predictionio_tpu.templates.recommendation import DataSourceParams

    return EngineParams(
        data_source_params=("", DataSourceParams(app_name="recapp")),
        algorithm_params_list=[
            ("als", ALSParams(rank=8, num_iterations=3, seed=0))],
    )


class TestShardedFlavor:
    def test_train_predict_device_resident(self, mem_storage):
        from predictionio_tpu.templates.recommendation import (
            Query, ShardedALSModel, sharded_engine_factory,
        )

        from predictionio_tpu.core.base import RETRAIN

        _seed()
        engine = sharded_engine_factory()
        params = _engine_params()
        persistable = engine.train(CTX, params, "t1")
        assert persistable == [RETRAIN]  # a sharded model never pickles
        [model] = engine.prepare_deploy(CTX, params, "t1", persistable)
        assert isinstance(model, ShardedALSModel)
        assert hasattr(model.user_factors, "sharding")  # device-resident
        algo = engine._algorithms(params)[0]
        result = algo.predict(model, Query(user="u1", num=5))
        assert 0 < len(result.item_scores) <= 5
        assert {s.item[0] for s in result.item_scores[:3]} <= {"a", "b"}
        # seen exclusion held on device
        uidx = model.user_map["u1"]
        seen_items = set(model.item_map.decode(model.seen[uidx]))
        full = algo.predict(model, Query(user="u1", num=50))
        assert not ({s.item for s in full.item_scores} & seen_items)

    def test_device_resident_matches_host_factors(self, mem_storage):
        """The factors kept sharded in HBM (``train_als_device``) give
        the predictions of the host-factors engine
        (``train_als_auto``) on the same events."""
        from predictionio_tpu.templates.recommendation import (
            ALSModel, Query, ShardedALSModel, engine_factory,
            sharded_engine_factory,
        )

        _seed()
        params = _engine_params()

        def deploy(engine, iid):
            persistable = engine.train(CTX, params, iid)
            [model] = engine.prepare_deploy(CTX, params, iid, persistable)
            return engine._algorithms(params)[0], model

        algo_h, model_h = deploy(engine_factory(), "dh")
        algo_d, model_d = deploy(sharded_engine_factory(), "dd")
        assert isinstance(model_h, ALSModel)
        assert isinstance(model_d, ShardedALSModel)
        assert hasattr(model_d.user_factors, "sharding")
        for u in ("u1", "u7", "u15"):
            rh = algo_h.predict(model_h, Query(user=u, num=5))
            rd = algo_d.predict(model_d, Query(user=u, num=5))
            assert [s.item for s in rd.item_scores] == \
                [s.item for s in rh.item_scores], u
            np.testing.assert_allclose(
                [s.score for s in rd.item_scores],
                [s.score for s in rh.item_scores], rtol=1e-3)

    def test_bucketed_device_resident_uneven_rows(self):
        """Regression: user/item counts NOT divisible by the model-axis
        size must still train (factor rows pad to the divisor; serving
        masks the pad rows)."""
        from predictionio_tpu.ops.als import bucket_ratings_pair
        from predictionio_tpu.ops.serving import DeviceTopK
        from predictionio_tpu.parallel.als_sharding import train_als_device

        rng = np.random.default_rng(4)
        n_u, n_i = 21, 13  # both odd: indivisible by model=2 and data
        rows = rng.integers(0, n_u, 300)
        cols = rng.integers(0, n_i, 300)
        vals = rng.random(300).astype(np.float32) + 0.5
        ub, ib = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        X, Y = train_als_device(ub, ib, ALSParams(rank=4,
                                                  num_iterations=2,
                                                  seed=0))
        assert X.shape[0] >= n_u and Y.shape[0] >= n_i
        srv = DeviceTopK(X, Y, None, n_users=n_u, n_items=n_i)
        idx, scores = srv.user_topk(3, 5)
        assert (idx < n_i).all() and np.isfinite(scores).all()

    def test_batch_predict_matches_per_query(self, mem_storage):
        """batch_predict groups user queries into users_topk dispatches;
        results must equal the per-query path, including blacklists,
        unknown users, and item-similarity queries mixed in."""
        from predictionio_tpu.templates.recommendation import (
            Query, sharded_engine_factory,
        )

        _seed()
        engine = sharded_engine_factory()
        params = _engine_params()
        persistable = engine.train(CTX, params, "tb")
        [model] = engine.prepare_deploy(CTX, params, "tb", persistable)
        algo = engine._algorithms(params)[0]
        some_item = model.item_map.decode(np.asarray([0]))[0]
        queries = [
            (0, Query(user="u1", num=5)),
            (1, Query(user="u2", num=5)),
            (2, Query(user="nobody", num=5)),            # unknown user
            (3, Query(user="u3", num=5, blacklist=(some_item,))),
            (4, Query(items=(some_item,), num=4)),        # similarity
            (5, Query(user="u4", num=3)),                 # different num
        ]
        batched = dict(algo.batch_predict(CTX, model, queries))
        for qx, q in queries:
            single = algo.predict(model, q)
            # the vmapped program may fuse differently -> ULP-level score
            # diffs; the recommended items and ranking must be identical
            assert [s.item for s in batched[qx].item_scores] == \
                [s.item for s in single.item_scores], f"query {qx} diverged"
            np.testing.assert_allclose(
                [s.score for s in batched[qx].item_scores],
                [s.score for s in single.item_scores], rtol=1e-5)
        assert batched[0].item_scores  # non-trivial results came back

    def test_retrain_persistence_mode(self, mem_storage):
        """Sharded models are never pickled: run_train stores RETRAIN and
        prepare_deploy retrains (persistence mode 3)."""
        from predictionio_tpu.core.base import RETRAIN
        from predictionio_tpu.templates.recommendation import (
            Query, ShardedALSModel, sharded_engine_factory,
        )
        from predictionio_tpu.workflow import (
            deserialize_models, run_train,
        )
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig, new_engine_instance,
        )

        _seed()
        engine = sharded_engine_factory()
        params = _engine_params()
        cfg = WorkflowConfig(engine_factory=SHARDED_FACTORY)
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=CTX)
        blob = storage.get_model_data_models().get(iid)
        [stored] = deserialize_models(blob.models)
        assert stored is RETRAIN
        restored = engine.prepare_deploy(CTX, params, iid, [stored])
        assert isinstance(restored[0], ShardedALSModel)
        algo = engine._algorithms(params)[0]
        assert algo.predict(restored[0], Query(user="u2", num=3)).item_scores

    def test_served_through_query_server(self, mem_storage):
        """Deploy the sharded engine and answer /queries.json — the model
        behind the HTTP server lives in HBM shards."""
        from predictionio_tpu.workflow import QueryServer, ServerConfig
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig, new_engine_instance,
        )
        from predictionio_tpu.templates.recommendation import (
            sharded_engine_factory,
        )
        from predictionio_tpu.workflow import run_train

        _seed()
        engine = sharded_engine_factory()
        params = _engine_params()
        cfg = WorkflowConfig(engine_factory=SHARDED_FACTORY)
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=CTX)
        assert iid is not None

        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            host, port = srv.address
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/queries.json",
                         body=json.dumps({"user": "u3", "num": 4}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = json.loads(resp.read().decode("utf-8"))
            conn.close()
            assert resp.status == 200
            assert 0 < len(data["itemScores"]) <= 4
        finally:
            srv.stop()
