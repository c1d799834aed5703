"""Differential suite for the pipelined ingest: the overlapped chain
(per-block sort -> k-way merge dedup -> fused bucketize -> async H2D)
must produce BYTE-IDENTICAL training inputs to the serial
StreamingRatingsBuilder + bucket_ratings_pair path — same BiMaps, same
bucket layouts, same final ALS factors — on randomized power-law
streams at every block size (including block_size > nnz and
single-event blocks). Plus the native-kernel-vs-numpy differentials,
the poisoned-partition exception propagation regression, and the
slow-marked CPU end-to-end smoke (write store -> pipelined ingest ->
one train iteration)."""

import numpy as np
import pytest

from predictionio_tpu.data.columnar import (
    ColumnarEvents,
    PipelinedRatingsBuilder,
    StreamingRatingsBuilder,
    ingest_ratings_pipelined,
    iter_blocks_threaded,
)


def power_law_stream(n, n_users, n_items, seed, with_nones=False):
    """(entity_ids, target_ids, values) with power-law popularity and
    guaranteed duplicate (user, item) pairs."""
    rng = np.random.default_rng(seed)
    user_p = 1.0 / np.arange(1, n_users + 1) ** 0.7
    user_p /= user_p.sum()
    item_p = 1.0 / np.arange(1, n_items + 1) ** 0.9
    item_p /= item_p.sum()
    users = rng.choice(n_users, size=n, p=user_p)
    items = rng.choice(n_items, size=n, p=item_p)
    vals = rng.integers(1, 6, size=n).astype(np.float32)
    ents = np.asarray([f"u{u}" for u in users], dtype=object)
    tgts = np.asarray([f"i{i}" for i in items], dtype=object)
    if with_nones:
        drop = rng.random(n) < 0.05
        tgts[drop] = None
    return ents, tgts, vals


def blocks_of(ents, tgts, vals, block_size):
    n = len(ents)
    for i in range(0, n, block_size):
        j = min(i + block_size, n)
        yield ColumnarEvents(
            entity_ids=ents[i:j], target_ids=tgts[i:j],
            values=vals[i:j], event_times=np.zeros(j - i))


def serial_reference(ents, tgts, vals, block_size, **bucket_kw):
    from predictionio_tpu.ops.als import bucket_ratings_pair

    b = StreamingRatingsBuilder()
    for blk in blocks_of(ents, tgts, vals, block_size):
        b.add_block(blk)
    um, im, rows, cols, v = b.finalize()
    us, its = bucket_ratings_pair(rows, cols, v, len(um), len(im),
                                  **bucket_kw)
    return um, im, us, its


def assert_sides_equal(a, b):
    assert a.n_rows == b.n_rows and a.n_cols == b.n_cols
    assert len(a.buckets) == len(b.buckets)
    for x, y in zip(a.buckets, b.buckets):
        np.testing.assert_array_equal(np.asarray(x.row_ids),
                                      np.asarray(y.row_ids))
        np.testing.assert_array_equal(np.asarray(x.cols),
                                      np.asarray(y.cols))
        np.testing.assert_array_equal(np.asarray(x.weights),
                                      np.asarray(y.weights))
        np.testing.assert_array_equal(np.asarray(x.mask),
                                      np.asarray(y.mask))


class TestPipelinedDifferential:
    # block sizes: single-event blocks, tiny, uneven, one block bigger
    # than the whole stream
    @pytest.mark.parametrize("block_size", [1, 7, 64, 333, 10_000])
    def test_identical_to_serial(self, block_size):
        ents, tgts, vals = power_law_stream(1500, 80, 40, seed=3)
        um_s, im_s, us_s, its_s = serial_reference(ents, tgts, vals,
                                                   block_size)
        res = ingest_ratings_pipelined(
            blocks_of(ents, tgts, vals, block_size))
        assert res.user_map.to_dict() == um_s.to_dict()
        assert res.item_map.to_dict() == im_s.to_dict()
        assert_sides_equal(res.user_side, us_s)
        assert_sides_equal(res.item_side, its_s)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_randomized_streams_with_missing_targets(self, seed):
        ents, tgts, vals = power_law_stream(2000, 60, 30, seed=seed,
                                            with_nones=True)
        um_s, im_s, us_s, its_s = serial_reference(ents, tgts, vals, 170)
        res = ingest_ratings_pipelined(blocks_of(ents, tgts, vals, 170))
        assert res.user_map.to_dict() == um_s.to_dict()
        assert res.item_map.to_dict() == im_s.to_dict()
        assert_sides_equal(res.user_side, us_s)
        assert_sides_equal(res.item_side, its_s)
        assert res.nnz == us_s.nnz

    def test_final_factors_identical(self):
        from predictionio_tpu.ops.als import ALSParams, train_als_bucketed

        ents, tgts, vals = power_law_stream(1200, 50, 25, seed=9)
        _, _, us_s, its_s = serial_reference(ents, tgts, vals, 111)
        params = ALSParams(rank=8, num_iterations=3, seed=4)
        X_s, Y_s = train_als_bucketed(us_s, its_s, params)
        res = ingest_ratings_pipelined(
            blocks_of(ents, tgts, vals, 111), stage_device=True,
            warmup_params=params).wait()
        X_p, Y_p = train_als_bucketed(res.user_side, res.item_side,
                                      params)
        np.testing.assert_array_equal(X_s, X_p)
        np.testing.assert_array_equal(Y_s, Y_p)

    def test_explicit_bucket_ladder_and_truncation(self):
        ents, tgts, vals = power_law_stream(1800, 40, 20, seed=5)
        kw = dict(bucket_lengths=[8, 32], max_len=48)
        um_s, im_s, us_s, its_s = serial_reference(ents, tgts, vals,
                                                   200, **kw)
        res = ingest_ratings_pipelined(blocks_of(ents, tgts, vals, 200),
                                       **kw)
        assert_sides_equal(res.user_side, us_s)
        assert_sides_equal(res.item_side, its_s)

    def test_empty_stream(self):
        res = ingest_ratings_pipelined(iter(()))
        assert res.nnz == 0 and res.n_events == 0
        assert len(res.user_map) == 0 and len(res.item_map) == 0
        assert res.user_side.buckets == [] or \
            all(len(b.row_ids) == 0 for b in res.user_side.buckets)

    def test_finalize_triples_contract_same_multiset(self):
        """PipelinedRatingsBuilder.finalize returns merged-sorted
        triples — same multiset as the serial stream order, and the
        deduped result matches exactly."""
        from predictionio_tpu.ops.als import dedup_sum_ratings

        ents, tgts, vals = power_law_stream(900, 30, 15, seed=11)
        sb, pb = StreamingRatingsBuilder(), PipelinedRatingsBuilder()
        for blk in blocks_of(ents, tgts, vals, 100):
            sb.add_block(blk)
        for blk in blocks_of(ents, tgts, vals, 100):
            pb.add_block(blk)
        um_s, im_s, r_s, c_s, v_s = sb.finalize()
        um_p, im_p, r_p, c_p, v_p = pb.finalize()
        assert um_p.to_dict() == um_s.to_dict()
        assert im_p.to_dict() == im_s.to_dict()
        d_s = dedup_sum_ratings(r_s, c_s, v_s, len(im_s))
        d_p = dedup_sum_ratings(r_p, c_p, v_p, len(im_p))
        for a, b in zip(d_s, d_p):
            np.testing.assert_array_equal(a, b)


class TestNativeKernelDifferentials:
    """Native merge/fill kernels vs the numpy oracle (skipped when the
    native toolchain is unavailable)."""

    def setup_method(self):
        from predictionio_tpu.native import codec

        if not codec.ingest_kernels_available():
            pytest.skip("native ingest kernels unavailable")

    def test_merge_permutation_matches_stable_argsort(self):
        from predictionio_tpu.native import codec

        rng = np.random.default_rng(2)
        runs = [np.sort(rng.integers(0, 500, size=int(n)))
                for n in rng.integers(0, 80, size=9)]
        keys = (np.concatenate(runs).astype(np.int64)
                if runs else np.empty(0, np.int64))
        offsets = np.cumsum([0] + [len(r) for r in runs]).astype(np.int64)
        perm = codec.merge_sorted_runs(keys, offsets)
        np.testing.assert_array_equal(perm,
                                      np.argsort(keys, kind="stable"))

    def test_segment_starts_matches_numpy(self):
        from predictionio_tpu.native import codec

        rng = np.random.default_rng(3)
        k = np.sort(rng.integers(0, 40, size=500)).astype(np.int64)
        ref = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
        np.testing.assert_array_equal(codec.segment_starts(k), ref)

    def test_bucketize_native_matches_python_oracle(self):
        """bucket_ratings_pair with the native fill vs the pure-numpy
        scatter (PIO_NATIVE_DISABLE in a subprocess oracle would be
        slow; instead compare against the in-process numpy fallback by
        rebuilding with the scatter code path)."""
        from predictionio_tpu.ops.als import bucket_ratings_pair
        from predictionio_tpu.native import codec as ncodec

        rng = np.random.default_rng(4)
        rows = rng.integers(0, 120, 4000)
        cols = rng.integers(0, 60, 4000)
        vals = rng.normal(size=4000).astype(np.float32)
        us_n, its_n = bucket_ratings_pair(rows, cols, vals, 120, 60)

        # numpy-oracle rebuild: force the fallback by hiding the lib
        real = ncodec._ingest_lib

        ncodec._ingest_lib = lambda: None
        try:
            us_py, its_py = bucket_ratings_pair(rows, cols, vals,
                                                120, 60)
        finally:
            ncodec._ingest_lib = real
        assert_sides_equal(us_n, us_py)
        assert_sides_equal(its_n, its_py)


class TestProducerFailurePropagation:
    def test_poisoned_partition_raises_not_hangs(self, tmp_path):
        """A partition whose decode raises (non-numeric value property
        under strict=True) must surface the error in the consumer —
        with a bounded queue and no leaked producer thread."""
        import threading

        from predictionio_tpu.data.storage.jsonlfs import JsonlFsPEvents

        pe = JsonlFsPEvents({"path": str(tmp_path),
                             "part_max_events": 4})
        pe._l.init(1)
        ok = ('{"event":"rate","entityType":"user","entityId":"u1",'
              '"targetEntityType":"item","targetEntityId":"i1",'
              '"properties":{"rating":3},'
              '"eventTime":"2020-01-01T00:00:00+00:00"}')
        poison = ok.replace('{"rating":3}', '{"rating":"BAD"}')
        pe._l.append_raw_lines([ok] * 4, 1)       # part 0: clean
        pe._l.append_raw_lines([ok, poison], 1)   # part 1: poisoned
        before = {t.ident for t in threading.enumerate()}
        with pytest.raises(ValueError, match="non-numeric"):
            list(iter_blocks_threaded(pe.find_columnar_blocks(
                1, event_names=["rate"], value_property="rating",
                strict=True, block_size=2), queue_size=2))
        # producer thread exits (no hang, no leak)
        for t in threading.enumerate():
            if t.ident in before:
                continue
            t.join(timeout=5)
            assert not t.is_alive(), f"leaked thread {t.name}"

    def test_poisoned_partition_with_prefetch(self, tmp_path):
        from predictionio_tpu.data.storage.jsonlfs import JsonlFsPEvents

        pe = JsonlFsPEvents({"path": str(tmp_path),
                             "part_max_events": 2})
        pe._l.init(1)
        ok = ('{"event":"rate","entityType":"user","entityId":"u1",'
              '"targetEntityType":"item","targetEntityId":"i1",'
              '"properties":{"rating":3},'
              '"eventTime":"2020-01-01T00:00:00+00:00"}')
        poison = ok.replace('{"rating":3}', '{"rating":[1]}')
        pe._l.append_raw_lines([ok, ok], 1)
        pe._l.append_raw_lines([poison], 1)
        pe._l.append_raw_lines([ok, ok], 1)
        with pytest.raises(ValueError, match="non-numeric"):
            for _ in pe.find_columnar_blocks(
                    1, event_names=["rate"], value_property="rating",
                    strict=True, prefetch=3):
                pass

    def test_pipelined_ingest_propagates_producer_error(self):
        def poisoned():
            ents, tgts, vals = power_law_stream(100, 10, 5, seed=1)
            yield from blocks_of(ents, tgts, vals, 40)
            raise RuntimeError("decode exploded")

        with pytest.raises(RuntimeError, match="decode exploded"):
            ingest_ratings_pipelined(poisoned())


class TestPrefetchScan:
    def test_prefetch_yields_identical_blocks(self, tmp_path):
        from predictionio_tpu.data.storage.jsonlfs import JsonlFsPEvents

        pe = JsonlFsPEvents({"path": str(tmp_path),
                             "part_max_events": 5})
        pe._l.init(1)
        lines = [
            ('{"event":"rate","entityType":"user","entityId":"u%d",'
             '"targetEntityType":"item","targetEntityId":"i%d",'
             '"properties":{"rating":%d},'
             '"eventTime":"2020-01-01T00:00:00+00:00"}')
            % (i % 7, i % 4, 1 + i % 5)
            for i in range(23)
        ]
        pe._l.append_raw_lines(lines, 1)

        def collect(prefetch):
            out = []
            for b in pe.find_columnar_blocks(
                    1, event_names=["rate"], value_property="rating",
                    block_size=3, prefetch=prefetch):
                m = b.materialize()
                out.append((list(m.entity_ids), list(m.target_ids),
                            m.values.tolist()))
            return out

        assert collect(0) == collect(2) == collect(8)


class TestTemplateWiring:
    def test_pipelined_datasource_matches_streaming(self, mem_storage):
        from predictionio_tpu.core.context import ComputeContext
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.templates.recommendation.engine import (
            DataSourceParams,
            EventDataSource,
            IndexedTrainingData,
        )
        from predictionio_tpu.ops.als import dedup_sum_ratings

        storage.get_metadata_apps().insert(App(0, "pipeapp"))
        app = storage.get_metadata_apps().get_by_name("pipeapp")
        lev = storage.get_levents()
        lev.init(app.id)
        import datetime as dt

        rng = np.random.default_rng(6)
        lev.insert_batch([
            Event(event="rate", entity_type="user",
                  entity_id=f"u{int(rng.integers(0, 9))}",
                  target_entity_type="item",
                  target_entity_id=f"i{int(rng.integers(0, 6))}",
                  properties={"rating": float(rng.integers(1, 6))},
                  event_time=dt.datetime(2020, 1, 1,
                                         tzinfo=dt.timezone.utc))
            for _ in range(200)], app.id)

        def read(pipelined):
            ds = EventDataSource(DataSourceParams(
                app_name="pipeapp", streaming_block_size=37,
                pipelined_ingest=pipelined, decode_prefetch=2))
            td = ds.read_training(ComputeContext())
            assert isinstance(td, IndexedTrainingData)
            return td

        td_s, td_p = read(False), read(True)
        assert td_p.user_map.to_dict() == td_s.user_map.to_dict()
        assert td_p.item_map.to_dict() == td_s.item_map.to_dict()
        # pipelined triples arrive merge-sorted; deduped they are
        # identical to the stream-ordered read's
        d_s = dedup_sum_ratings(td_s.rows, td_s.cols, td_s.values,
                                len(td_s.item_map))
        d_p = dedup_sum_ratings(td_p.rows, td_p.cols, td_p.values,
                                len(td_p.item_map))
        for a, b in zip(d_s, d_p):
            np.testing.assert_array_equal(a, b)

        # regression (review finding): read_eval's leave-last-out split
        # is ORDER-sensitive and must not change under pipelined_ingest
        # (the eval read forces the serial builder)
        def eval_split(pipelined):
            ds = EventDataSource(DataSourceParams(
                app_name="pipeapp", streaming_block_size=37,
                pipelined_ingest=pipelined))
            sets = ds.read_eval(ComputeContext())
            (_, _, qa), = sets
            return sorted((q.user, a.items[0]) for q, a in qa)

        assert eval_split(True) == eval_split(False)

    def test_pipelined_without_streaming_is_loud(self, mem_storage):
        from predictionio_tpu.core.context import ComputeContext
        from predictionio_tpu.templates.recommendation.engine import (
            DataSourceParams,
            EventDataSource,
        )

        ds = EventDataSource(DataSourceParams(
            app_name="nostream", pipelined_ingest=True))
        with pytest.raises(ValueError,
                           match="requires streaming_block_size"):
            ds.read_training(ComputeContext())


def _write_scale_store(tmp: str, n_users: int, n_items: int, nnz: int,
                       seed: int):
    """A partitioned jsonlfs store of power-law ``rate`` events."""
    from predictionio_tpu.data.storage.jsonlfs import JsonlFsPEvents

    rng = np.random.default_rng(seed)
    item_p = 1.0 / np.arange(1, n_items + 1) ** 0.8
    item_p /= item_p.sum()
    user_p = 1.0 / np.arange(1, n_users + 1) ** 0.6
    user_p /= user_p.sum()
    pe = JsonlFsPEvents({"path": tmp, "part_max_events": 1_000_000})
    pe._l.init(1)
    rs = rng.choice(n_users, size=nnz, p=user_p)
    cs = rng.choice(n_items, size=nnz, p=item_p)
    vs = rng.integers(1, 6, size=nnz)
    pe._l.append_raw_lines(
        [f'{{"event":"rate","entityType":"user","entityId":"u{r}",'
         f'"targetEntityType":"item","targetEntityId":"i{c}",'
         f'"properties":{{"rating":{v}}},'
         f'"eventTime":"2020-01-01T00:00:00+00:00"}}'
         for r, c, v in zip(rs, cs, vs)], 1)
    return pe


@pytest.mark.slow
class TestEndToEndSmoke:
    def test_store_to_train_one_iteration(self, tmp_path):
        """CI smoke: write a partitioned store, pipelined ingest with
        device staging + warm-up, one bucketed train iteration — all on
        CPU."""
        from predictionio_tpu.ops.als import ALSParams, train_als_bucketed

        pe = _write_scale_store(str(tmp_path), 300, 80, 20_000, 21)
        params = ALSParams(rank=8, num_iterations=1, seed=2)
        res = ingest_ratings_pipelined(
            pe.find_columnar_blocks(
                1, event_names=["rate"], value_property="rating",
                block_size=4096, prefetch=2),
            stage_device=True, warmup_params=params).wait()
        assert res.n_events == 20_000
        assert res.nnz > 0
        X, Y = train_als_bucketed(res.user_side, res.item_side, params)
        assert X.shape == (len(res.user_map), 8)
        assert Y.shape == (len(res.item_map), 8)
        assert np.isfinite(X).all() and np.isfinite(Y).all()
        # the overlap evidence made it into the timeline
        stages = res.timeline.summary()["stages"]
        for stage in ("decode", "index", "merge", "bucket.user",
                      "bucket.item"):
            assert stage in stages, stages.keys()
