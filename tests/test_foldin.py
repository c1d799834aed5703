"""Online fold-in suite (PR 8): storage tail reads on all four event
backends, the batch-k fold-in kernel's differential oracle against full
``train_als_bucketed`` rows, live-store patch atomicity under concurrent
serving, the ``--foldin`` serving-backend policy, ``/reload`` hardening, and the
deployed end-to-end path (event -> servable in seconds, degradation when
the tail fails)."""

import datetime as dt
import http.client
import json
import threading
import time
import urllib.parse

import numpy as np
import pytest

from predictionio_tpu.data import storage
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import AccessKey, App
from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings,
    bucket_ratings_pair,
    fold_in_users,
    init_factors,
    train_als_bucketed,
)

pytestmark = pytest.mark.online

UTC = dt.timezone.utc


def t(i):
    return dt.datetime(2022, 5, 1, tzinfo=UTC) + dt.timedelta(seconds=int(i))


def rate_event(u, i, val=4.0, at=0):
    return Event(event="rate", entity_type="user", entity_id=str(u),
                 target_entity_type="item", target_entity_id=str(i),
                 properties={"rating": float(val)}, event_time=t(at))


# ---------------------------------------------------------------------------
# Tail reads: find_since / tail_cursor / tail_watermark on every backend
# ---------------------------------------------------------------------------

@pytest.fixture(params=["memory", "sqlite", "jsonlfs"])
def local_levents(request, tmp_path):
    if request.param == "memory":
        from predictionio_tpu.data.storage.memory import MemLEvents

        le = MemLEvents()
    elif request.param == "sqlite":
        from predictionio_tpu.data.storage.sqlite import SqliteLEvents

        le = SqliteLEvents({"path": str(tmp_path / "tail.db")})
    else:
        from predictionio_tpu.data.storage.jsonlfs import JsonlFsLEvents

        # tiny partitions so the tail crosses partition rolls
        le = JsonlFsLEvents({"path": str(tmp_path / "events"),
                             "part_max_events": 4})
    le.init(1)
    yield le
    shutdown = getattr(le, "shutdown", None)
    if callable(shutdown):
        shutdown()


class TestFindSinceLocal:
    def test_delta_after_cursor_in_arrival_order(self, local_levents):
        le = local_levents
        first = [rate_event(f"u{i}", f"i{i}", at=i) for i in range(6)]
        le.insert_batch(first, 1)
        cur = le.tail_cursor(1)
        second = [rate_event(f"v{i}", f"j{i}", at=100 + i)
                  for i in range(7)]
        ids = le.insert_batch(second, 1)
        got, cur2 = le.find_since(1, cursor=cur)
        assert [e.event_id for e in got] == ids
        # the advanced cursor is exactly at the end: nothing more
        again, cur3 = le.find_since(1, cursor=cur2)
        assert again == []

    def test_none_cursor_replays_from_start(self, local_levents):
        le = local_levents
        ids = le.insert_batch(
            [rate_event(f"u{i}", "x", at=i) for i in range(5)], 1)
        got, _ = le.find_since(1)
        assert [e.event_id for e in got] == ids

    def test_limit_bounds_and_resumes_exactly(self, local_levents):
        le = local_levents
        ids = le.insert_batch(
            [rate_event(f"u{i}", "x", at=i) for i in range(9)], 1)
        cur, seen = None, []
        for _ in range(20):
            got, cur = le.find_since(1, cursor=cur, limit=2)
            if not got:
                break
            assert len(got) <= 2
            seen.extend(e.event_id for e in got)
        assert seen == ids

    def test_tail_watermark_names_last_event(self, local_levents):
        le = local_levents
        wm0 = le.tail_watermark(1)
        assert wm0["lastEventId"] is None
        ids = le.insert_batch(
            [rate_event(f"u{i}", "x", at=i) for i in range(5)], 1)
        wm = le.tail_watermark(1)
        assert wm["lastEventId"] == ids[-1]
        assert wm["lastEventTime"] is not None
        # the watermark's cursor is an end cursor
        got, _ = le.find_since(1, cursor=wm["cursor"])
        assert got == []

    def test_trim_then_reingest_never_skips(self, local_levents):
        """Recycled-position hazard: a delete_until that frees the TAIL
        of the store (sqlite reuses rowids past MAX; jsonlfs partition
        names survive rewrites) followed by re-ingest that grows back
        past the old cursor must replay, never silently skip the events
        re-landed under the cursor's position."""
        le = local_levents
        # arrival order deliberately disagrees with event time: the
        # LAST-arrived events carry the OLDEST times, so the time-based
        # trim frees the newest storage positions
        le.insert_batch([rate_event(f"a{i}", "x", at=100 + i)
                         for i in range(4)], 1)
        le.insert_batch([rate_event(f"b{i}", "x", at=i)
                         for i in range(2)], 1)
        cur = le.tail_cursor(1)
        assert le.delete_until(1, t(50)) == 2
        new_ids = le.insert_batch([rate_event(f"c{i}", "x", at=200 + i)
                                   for i in range(6)], 1)
        seen, cur2 = [], cur
        for _ in range(10):
            got, cur2 = le.find_since(1, cursor=cur2)
            if not got:
                break
            seen.extend(e.event_id for e in got)
        missed = [eid for eid in new_ids if eid not in seen]
        assert not missed, f"tail consumer silently skipped {missed}"

    def test_store_rewrite_resets_cursor_to_replay(self, local_levents):
        le = local_levents
        le.insert_batch([rate_event(f"u{i}", "x", at=i)
                         for i in range(4)], 1)
        cur = le.tail_cursor(1)
        le.remove(1)
        le.init(1)
        ids = le.insert_batch([rate_event("w", "y", at=50)], 1)
        got, _ = le.find_since(1, cursor=cur)
        # replay-tolerant contract: after a rewrite the stale cursor
        # replays (never silently misses the new event)
        assert ids[0] in [e.event_id for e in got]

    def test_remove_reingest_past_cursor_replays(self, local_levents):
        """Same contract, harder case: the re-ingested stream grows
        PAST the old cursor's position, so a bare position/size check
        looks valid — only a generation (or equivalent) can tell the
        positions now hold different events."""
        le = local_levents
        le.insert_batch([rate_event(f"u{i}", "x", at=i)
                         for i in range(4)], 1)
        cur = le.tail_cursor(1)
        le.remove(1)
        le.init(1)
        ids = le.insert_batch([rate_event(f"w{i}", "y", at=50 + i)
                               for i in range(7)], 1)
        seen, cur2 = [], cur
        for _ in range(5):
            got, cur2 = le.find_since(1, cursor=cur2)
            if not got:
                break
            seen.extend(e.event_id for e in got)
        missed = [eid for eid in ids if eid not in seen]
        assert not missed, f"tail consumer silently skipped {missed}"


class TestMemorySeqCompaction:
    def test_retention_trim_bounds_seq_and_cursors_replay(self):
        """The memory backend's arrival sequence must not grow one dead
        entry per ever-deleted event (long-lived server + periodic
        delete_until retention trimming), and compaction — which
        renumbers positions — must bump the generation so outstanding
        cursors replay instead of skipping."""
        from predictionio_tpu.data.storage.memory import MemLEvents

        le = MemLEvents()
        le.init(1)
        le.insert_batch([rate_event(f"u{i}", "x", at=i)
                         for i in range(100)], 1)
        cur = le.tail_cursor(1)
        assert le.delete_until(1, t(90)) == 90
        # tombstones compacted: bounded by live events, not history
        assert len(le._seq[(1, None)]) <= 64
        new_ids = le.insert_batch([rate_event(f"n{i}", "y", at=200 + i)
                                   for i in range(3)], 1)
        seen, cur2 = [], cur
        for _ in range(5):
            got, cur2 = le.find_since(1, cursor=cur2)
            if not got:
                break
            seen.extend(e.event_id for e in got)
        # the pre-trim cursor replays (gen bumped) and misses nothing
        assert all(eid in seen for eid in new_ids)


class TestFindSinceRestHttp:
    KEY = "tail-secret"

    @pytest.fixture
    def wire_levents(self, mem_storage):
        from predictionio_tpu.data.api import (
            EventServer,
            EventServerConfig,
        )
        from predictionio_tpu.data.storage.resthttp import RestLEvents

        server = EventServer(EventServerConfig(
            ip="127.0.0.1", port=0, service_key=self.KEY),
            reg=mem_storage).start()
        url = f"http://{server.address[0]}:{server.address[1]}"
        le = RestLEvents({"url": url, "service_key": self.KEY})
        yield le
        server.stop()

    def test_cursor_round_trips_the_wire(self, wire_levents):
        le = wire_levents
        le.init(9)
        le.insert_batch([rate_event(f"u{i}", "x", at=i)
                         for i in range(3)], 9)
        cur = le.tail_cursor(9)
        assert cur  # the remote backend's opaque cursor
        ids = le.insert_batch([rate_event("fresh", "y", at=10)], 9)
        got, cur2 = le.find_since(9, cursor=cur)
        assert [e.event_id for e in got] == ids
        assert le.find_since(9, cursor=cur2)[0] == []
        wm = le.tail_watermark(9)
        assert wm["lastEventId"] == ids[-1]

    def test_limit_over_the_wire(self, wire_levents):
        le = wire_levents
        le.init(9)
        ids = le.insert_batch([rate_event(f"u{i}", "x", at=i)
                               for i in range(5)], 9)
        got, cur = le.find_since(9, limit=2)
        assert [e.event_id for e in got] == ids[:2]
        got2, _ = le.find_since(9, cursor=cur, limit=10)
        assert [e.event_id for e in got2] == ids[2:]


# ---------------------------------------------------------------------------
# The differential oracle: fold-in == the full training half-step
# ---------------------------------------------------------------------------

def _ragged_sets(rows, cols, vals, users):
    cl, vl = [], []
    for u in users:
        sel = rows == u
        cl.append(cols[sel])
        vl.append(vals[sel])
    return cl, vl


class TestFoldInDifferential:
    """``train_als_bucketed`` solves X against the initial Y in its FIRST
    half-iteration — so with ``num_iterations=1`` the returned user rows
    ARE "the full retrain's user rows given fixed item factors"
    (``init_factors`` is seed-deterministic, handing the oracle the
    exact fixed Y). The fold-in kernel must reproduce them from each
    user's raw rating set, at its own (different) padding/bucketing."""

    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    @pytest.mark.parametrize("implicit", [True, False])
    def test_implicit_and_explicit_lanes(self, precision, implicit):
        rng = np.random.default_rng(11)
        n_u, n_i, nnz = 40, 25, 500
        rows = rng.integers(0, n_u, nnz)
        cols = rng.integers(0, n_i, nnz)
        vals = rng.uniform(1, 5, nnz).astype(np.float32)
        params = ALSParams(rank=8, num_iterations=1, seed=5,
                           implicit_prefs=implicit, precision=precision)
        us, it = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        X1, _ = train_als_bucketed(us, it, params)
        _, Y0 = init_factors(n_u, n_i, 8, 5)
        touched = rng.choice(n_u, size=9, replace=False)
        folded = fold_in_users(
            np.asarray(Y0), *_ragged_sets(rows, cols, vals, touched),
            params)
        scale = max(1.0, float(np.abs(X1).max()))
        tol = (1e-4 if precision == "fp32" else 4 * 2 ** -8) * scale
        assert np.abs(folded - X1[touched]).max() < tol

    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    def test_bucketed_lane(self, precision):
        rng = np.random.default_rng(3)
        n_u, n_i, nnz = 60, 30, 900
        rows = rng.integers(0, n_u, nnz)
        cols = rng.integers(0, n_i, nnz)
        vals = rng.uniform(1, 5, nnz).astype(np.float32)
        params = ALSParams(rank=8, num_iterations=1, seed=2,
                           precision=precision)
        us, it = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        X1, _ = train_als_bucketed(us, it, params)
        _, Y0 = init_factors(n_u, n_i, 8, 2)
        touched = rng.choice(n_u, size=7, replace=False)
        folded = fold_in_users(
            np.asarray(Y0), *_ragged_sets(rows, cols, vals, touched),
            params)
        scale = max(1.0, float(np.abs(X1).max()))
        tol = (1e-4 if precision == "fp32" else 4 * 2 ** -8) * scale
        assert np.abs(folded - X1[touched]).max() < tol

    def test_max_len_truncation_parity(self):
        """An engine trained with preparator max_len truncates every
        user row to the largest-magnitude ratings BEFORE solving; the
        fold must apply the same cut or long-history users solve a
        different objective than their trained rows."""
        rng = np.random.default_rng(17)
        # ~26 distinct ratings/user; max_len=10 is deliberately NOT a
        # multiple of the tables' pad_multiple (8): training rounds the
        # cap up to 16 before cutting, and the fold must cut at the same
        # EFFECTIVE cap — truncating at the raw 10 silently solves a
        # smaller problem than the trained rows did
        n_u, n_i, nnz = 20, 30, 600
        rows = rng.integers(0, n_u, nnz)
        cols = rng.integers(0, n_i, nnz)
        vals = rng.uniform(1, 5, nnz).astype(np.float32)
        params = ALSParams(rank=6, num_iterations=1, seed=9)
        us = bucket_ratings(rows, cols, vals, n_u, n_i, max_len=10)
        it = bucket_ratings(cols, rows, vals, n_i, n_u)
        X1, _ = train_als_bucketed(us, it, params)
        _, Y0 = init_factors(n_u, n_i, 6, 9)
        touched = rng.choice(n_u, size=6, replace=False)
        folded = fold_in_users(
            np.asarray(Y0), *_ragged_sets(rows, cols, vals, touched),
            params, max_len=10)
        scale = max(1.0, float(np.abs(X1).max()))
        assert np.abs(folded - X1[touched]).max() < 1e-4 * scale
        # and WITHOUT the cap the fold diverges for truncated users —
        # the parity above is load-bearing, not vacuous
        unfolded = fold_in_users(
            np.asarray(Y0), *_ragged_sets(rows, cols, vals, touched),
            params)
        assert np.abs(unfolded - X1[touched]).max() > 1e-3 * scale

    def test_duplicates_summed_like_training(self):
        # the same (user, item) rated twice must fold as the SUM
        # (reduceByKey parity with the training tables)
        params = ALSParams(rank=4, num_iterations=1, seed=1)
        _, Y0 = init_factors(4, 6, 4, 1)
        dup = fold_in_users(np.asarray(Y0),
                            [np.array([2, 2, 3])],
                            [np.array([1.5, 2.5, 1.0], np.float32)],
                            params)
        summed = fold_in_users(np.asarray(Y0),
                               [np.array([2, 3])],
                               [np.array([4.0, 1.0], np.float32)],
                               params)
        np.testing.assert_allclose(dup, summed, atol=1e-6)

    def test_empty_and_unknown_only_users_are_zero(self):
        params = ALSParams(rank=4, num_iterations=1, seed=1)
        _, Y0 = init_factors(4, 6, 4, 1)
        out = fold_in_users(np.asarray(Y0), [np.array([], np.int64)],
                            [np.array([], np.float32)], params)
        assert out.shape == (1, 4)
        np.testing.assert_array_equal(out, 0.0)


# ---------------------------------------------------------------------------
# Live-store patching: atomicity under fire, growth, seen masking
# ---------------------------------------------------------------------------

class TestPatchUsers:
    def _server(self, X, Y, seen=None, microbatch=False):
        from predictionio_tpu.ops.serving import DeviceTopK

        return DeviceTopK(X, Y, seen, microbatch=microbatch)

    def test_patch_replaces_row_and_seen(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(8, 4)).astype(np.float32)
        Y = rng.normal(size=(6, 4)).astype(np.float32)
        srv = self._server(X, Y, {u: np.array([5]) for u in range(8)})
        row = rng.normal(size=(1, 4)).astype(np.float32)
        srv.patch_users(np.array([2]), row,
                        seen_items={2: np.array([0, 1])})
        idx, scores = srv.user_topk(2, 6)
        exp = Y @ row[0]
        exp[[0, 1]] = -np.inf
        order = np.argsort(-exp)[:4]
        np.testing.assert_array_equal(idx, order)
        assert 0 not in idx and 1 not in idx and 5 in idx

    def test_growth_via_bucket_ladder(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(10, 4)).astype(np.float32)
        Y = rng.normal(size=(6, 4)).astype(np.float32)
        srv = self._server(X, Y)
        row = np.ones((1, 4), dtype=np.float32)
        srv.patch_users(np.array([21]), row)
        assert srv.user_capacity == 32  # 10 -> 16? no: lo=max(10,16)=16 -> 32
        assert srv.n_users == 22
        idx, scores = srv.user_topk(21, 3)
        exp = np.argsort(-(Y @ row[0]))[:3]
        np.testing.assert_array_equal(idx, exp)
        # ungrown rows still serve
        idx0, _ = srv.user_topk(0, 3)
        np.testing.assert_array_equal(
            idx0, np.argsort(-(Y @ X[0]))[:3])

    def test_seenless_growth_grows_seen_tables_too(self):
        """A seen-masked store grown by a patch WITHOUT seen updates
        must still grow its seen tables: a new uid with no seen row of
        its own would clamp into the last existing user's row at gather
        time and serve someone else's masking."""
        rng = np.random.default_rng(4)
        X = rng.normal(size=(8, 4)).astype(np.float32)
        Y = rng.normal(size=(6, 4)).astype(np.float32)
        # user 7 has seen item 0 — the clamp target if tables lag
        srv = self._server(X, Y, {u: np.array([0]) for u in range(8)})
        row = rng.normal(size=(1, 4)).astype(np.float32)
        srv.patch_users(np.array([15]), row)  # grows, no seen_items
        assert srv._seen_bits.shape[0] == srv.user_capacity
        idx, _ = srv.user_topk(15, 6)
        exp = np.argsort(-(Y @ row[0]))[:6]
        # nothing masked for the new user — item 0 ranks wherever the
        # scores put it, not forced out by user 7's seen row
        np.testing.assert_array_equal(np.sort(idx), np.sort(exp))

    def test_serve_during_patch_never_torn(self):
        """Continuous ``user_topk`` traffic across rapid patches sees
        either the OLD row's exact top-k or the NEW row's — never a
        mixture or garbage (the micro-batch/store-swap coordination
        contract)."""
        rng = np.random.default_rng(2)
        Y = rng.normal(size=(32, 8)).astype(np.float32)
        A = rng.normal(size=(1, 8)).astype(np.float32)
        B = -A  # guaranteed-distinct ranking
        X = np.tile(A, (4, 1))
        srv = self._server(X, Y, microbatch=True)
        top = {}
        for name, row in (("A", A), ("B", B)):
            s = Y @ row[0]
            top[name] = tuple(np.argsort(-s)[:8])
        results, errors = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    idx, scores = srv.user_topk(0, 8)
                    if not np.isfinite(scores).all():
                        errors.append("nonfinite")
                    results.append(tuple(idx))
                except Exception as e:  # pragma: no cover - fails test
                    errors.append(repr(e))

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            for k in range(60):
                srv.patch_users(np.array([0]), A if k % 2 else B)
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=10)
        srv.close()
        assert not errors
        assert results
        legal = {top["A"], top["B"]}
        assert set(results) <= legal

    def test_bf16_store_accepts_fp32_rows(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        rng = np.random.default_rng(3)
        X = rng.normal(size=(8, 4)).astype(np.float32)
        Y = rng.normal(size=(6, 4)).astype(np.float32)
        srv = self._server(X, Y)
        assert srv._X.dtype.name == "bfloat16"
        srv.patch_users(np.array([1]), np.ones((1, 4), np.float32))
        assert srv._X.dtype.name == "bfloat16"
        idx, scores = srv.user_topk(1, 3)
        assert scores.dtype == np.float32 and np.isfinite(scores).all()


class TestFoldBatchRetry:
    def test_failed_fold_batch_is_requeued(self):
        """The cursor has already advanced past a batch's events when
        the fold runs, so a failed fold (transient storage error in the
        gather, a solve/patch blow-up) must put the touched users BACK —
        dropping them would leave a new user unservable until their next
        event, indefinitely."""
        from predictionio_tpu.online.foldin import (
            FoldInConfig,
            FoldInConsumer,
        )

        consumer = FoldInConsumer(None, FoldInConfig(app_name="x"),
                                  ALSParams(rank=4))
        consumer._pending = {"u1": 2, "u2": 1}
        consumer._pending_events = 3
        consumer._fresh_ts = [1.0, 2.0]

        def boom(uids):
            raise RuntimeError("transient gather failure")

        consumer._gather = boom
        consumer._fold()
        assert consumer.fold_errors == 1
        # nothing lost: the whole batch retries at the next cadence
        assert consumer._pending == {"u1": 2, "u2": 1}
        assert consumer._pending_events == 3
        assert consumer._fresh_ts == [1.0, 2.0]
        # ...but a batch that KEEPS failing is dropped at the cap — a
        # poison user must not stop every other user's folds forever
        consumer._fold()
        assert consumer._pending  # attempt 2: still retrying
        consumer._fold()
        assert consumer._pending == {}  # attempt 3: dropped
        assert consumer.fold_errors == 3


class TestGatherPaths:
    def test_scan_and_indexed_paths_agree(self, mem_storage):
        """Beyond a handful of touched users on a scan-based backend the
        gather switches from per-user finds to ONE shared scan bucketed
        client-side — both paths must produce identical rating sets."""
        from predictionio_tpu.online.foldin import (
            FoldInConfig,
            FoldInConsumer,
        )

        apps = storage.get_metadata_apps()
        aid = apps.insert(App(0, "gatherapp"))
        le = storage.get_levents()
        le.init(aid)
        rng = np.random.default_rng(5)
        le.insert_batch(
            [rate_event(f"u{i % 7}", f"i{int(rng.integers(0, 9))}",
                        val=float(rng.integers(1, 6)), at=i)
             for i in range(60)], aid)

        class Stub:
            item_map = {f"i{j}": j for j in range(9)}

        c = FoldInConsumer(Stub(), FoldInConfig(app_name="gatherapp"),
                           ALSParams(rank=4))
        c._scope = (aid, None)
        uids = [f"u{i}" for i in range(7)]  # >4 -> scan path on memory
        scan_kept, scan_cols, scan_vals = c._gather(list(uids))
        le.indexed_entity_reads = True  # force the per-user path
        try:
            idx_kept, idx_cols, idx_vals = c._gather(list(uids))
        finally:
            del le.indexed_entity_reads
        assert scan_kept == idx_kept
        for a, b in zip(scan_cols, idx_cols):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(scan_vals, idx_vals):
            np.testing.assert_array_equal(a, b)


class TestChooseServerFoldinPolicy:
    def test_foldin_forces_device(self, monkeypatch):
        from predictionio_tpu.ops.serving import DeviceTopK, choose_server

        monkeypatch.setenv("PIO_FOLDIN", "on")
        X = np.ones((4, 2), np.float32)
        Y = np.ones((3, 2), np.float32)
        srv = choose_server(X, Y)  # small: auto would pick HostTopK
        assert isinstance(srv, DeviceTopK)

    def test_host_plus_foldin_raises(self, monkeypatch):
        from predictionio_tpu.ops.serving import choose_server

        monkeypatch.setenv("PIO_FOLDIN", "1")
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        with pytest.raises(ValueError, match="fold-in|PIO_FOLDIN"):
            choose_server(np.ones((4, 2), np.float32),
                          np.ones((3, 2), np.float32))

    def test_off_keeps_auto_host(self, monkeypatch):
        from predictionio_tpu.ops.serving import HostTopK, choose_server

        monkeypatch.delenv("PIO_FOLDIN", raising=False)
        srv = choose_server(np.ones((4, 2), np.float32),
                            np.ones((3, 2), np.float32))
        assert isinstance(srv, HostTopK)


# ---------------------------------------------------------------------------
# Query-server integration: reload hardening + deployed fold-in
# ---------------------------------------------------------------------------

def _post(addr, path, body, params=None):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    target = path + ("?" + urllib.parse.urlencode(params) if params else "")
    conn.request("POST", target, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode("utf-8"))
    conn.close()
    return resp.status, data


def _get(addr, path):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = json.loads(resp.read().decode("utf-8"))
    conn.close()
    return resp.status, data


def _seed_app(app_name, n_users=16, n_items=12, per_user=6, seed=0):
    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(seed)
    evs = []
    for u in range(n_users):
        for i in rng.choice(n_items, size=per_user, replace=False):
            evs.append(rate_event(f"u{u}", f"i{int(i)}",
                                  val=float(rng.integers(3, 6)), at=u))
    le.insert_batch(evs, aid)
    return aid


def _train(app_name, seed=0):
    from predictionio_tpu.controller import ComputeContext, EngineParams
    from predictionio_tpu.templates.recommendation import (
        DataSourceParams,
        engine_factory,
    )
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig,
        new_engine_instance,
    )

    engine = engine_factory()
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name=app_name)),
        algorithm_params_list=[
            ("als", ALSParams(rank=8, num_iterations=3, seed=seed))],
    )
    factory = "predictionio_tpu.templates.recommendation:engine_factory"
    config = WorkflowConfig(engine_factory=factory)
    instance = new_engine_instance(config, params)
    iid = run_train(engine, params, instance, ctx=ComputeContext())
    assert iid is not None
    return iid


class TestReloadHardening:
    def test_reload_reports_swap_and_refuses_downgrade(self, mem_storage):
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        _seed_app("recapp")
        iid1 = _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            iid2 = _train("recapp")
            status, data = _post(srv.address, "/reload", {})
            assert status == 200
            assert data["engineInstanceId"] == iid2
            assert data["swappedFrom"] == iid1
            assert data["swappedTo"] == iid2
            # delete the newer instance record: "latest completed" is
            # now OLDER than the deployed one -> refuse with 409
            storage.get_metadata_engine_instances().delete(iid2)
            status, data = _post(srv.address, "/reload", {})
            assert status == 409
            assert "refusing" in data["message"]
            # the deployed instance is untouched and still serves
            _, page = _get(srv.address, "/")
            assert page["engineInstanceId"] == iid2
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1"})
            assert status == 200
        finally:
            srv.stop()

    def test_reload_of_resumed_train_matches_clean(self, mem_storage,
                                                   tmp_path,
                                                   monkeypatch):
        """Crash-safe-training regression: a train that was PREEMPTED
        at a chunk boundary and resumed to completion reloads exactly
        like a clean train — same /reload response shape, same swap
        accounting, and (training being deterministic under the
        checkpoint fingerprint) byte-identical query results."""
        from predictionio_tpu.workflow import (
            QueryServer,
            ServerConfig,
            TrainingPreempted,
            checkpoint,
        )

        _seed_app("recapp")
        iid_clean = _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            status, clean_result = _post(srv.address, "/queries.json",
                                         {"user": "u1", "num": 5})
            assert status == 200 and clean_result["itemScores"]

            # preempt a second train after its first chunk, then
            # resume it to completion (the kill-9 lifecycle, in-process)
            monkeypatch.setenv("PIO_CHECKPOINT_DIR",
                               str(tmp_path / "ck"))
            monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "1")
            checkpoint.request_stop()
            try:
                with pytest.raises(TrainingPreempted):
                    _train("recapp")
            finally:
                checkpoint.clear_stop()
            monkeypatch.setenv("PIO_RESUME", "1")
            iid_resumed = _train("recapp")
            monkeypatch.delenv("PIO_CHECKPOINT_DIR")

            # the resumed-then-completed instance reloads exactly like
            # a clean one: 200, correct swap bookkeeping, no downgrade
            status, data = _post(srv.address, "/reload", {})
            assert status == 200
            assert data["engineInstanceId"] == iid_resumed
            assert data["swappedFrom"] == iid_clean
            assert data["swappedTo"] == iid_resumed
            status, resumed_result = _post(srv.address, "/queries.json",
                                           {"user": "u1", "num": 5})
            assert status == 200
            assert resumed_result["itemScores"] == \
                clean_result["itemScores"]
        finally:
            srv.stop()


@pytest.fixture
def foldin_env(monkeypatch):
    monkeypatch.setenv("PIO_FOLDIN", "1")
    monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")


class TestFoldInDeployed:
    def _wait_servable(self, srv_addr, user, deadline_sec=20):
        t0 = time.time()
        while time.time() - t0 < deadline_sec:
            status, result = _post(srv_addr, "/queries.json",
                                   {"user": user, "num": 5})
            assert status == 200
            if result.get("itemScores"):
                return time.time() - t0, result
            time.sleep(0.05)
        pytest.fail(f"user {user} never became servable")

    def test_new_user_servable_without_reload(self, mem_storage,
                                              foldin_env):
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        aid = _seed_app("recapp")
        _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            # unknown before any events
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "fresh1"})
            assert status == 200 and result["itemScores"] == []
            le = storage.get_levents()
            le.insert_batch([rate_event("fresh1", f"i{i}", val=5.0,
                                        at=1000 + i) for i in range(3)],
                            aid)
            took, result = self._wait_servable(srv.address, "fresh1")
            # the user's own rated items are seen-masked out
            items = {s["item"] for s in result["itemScores"]}
            assert items.isdisjoint({"i0", "i1", "i2"})
            # an EXISTING user re-rating gets re-solved too
            le.insert(rate_event("u1", "i9", val=5.0, at=2000), aid)
            deadline = time.time() + 10
            while time.time() < deadline:
                _, page = _get(srv.address, "/")
                if page["foldin"]["usersPatched"] >= 2:
                    break
                time.sleep(0.05)
            _, page = _get(srv.address, "/")
            fi = page["foldin"]
            assert fi["folds"] >= 1 and fi["newUsers"] >= 1
            assert fi["stale"] is False
            # stats.json carries the fold-in block + metrics families
            _, stats = _get(srv.address, "/stats.json")
            assert stats["foldin"]["usersPatched"] >= 1
            assert "pio_foldin_folds_total" in stats["metrics"]
        finally:
            srv.stop()

    def test_embedder_foldin_without_env(self, mem_storage, monkeypatch):
        """ServerConfig(foldin=True) alone must work: an embedder that
        never goes through `pio deploy --foldin on` still needs
        choose_server to see the policy (deploy() sets it before the
        model loads), or a small host-capable model would pick HostTopK
        and the consumer would refuse to start."""
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.delenv("PIO_FOLDIN", raising=False)
        monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
        aid = _seed_app("recapp")
        _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            assert srv._foldin is not None
            le = storage.get_levents()
            le.insert_batch([rate_event("emb1", f"i{i}", val=5.0,
                                        at=3000 + i) for i in range(3)],
                            aid)
            self._wait_servable(srv.address, "emb1")
        finally:
            srv.stop()

    def test_tail_failure_degrades_and_recovers(self, mem_storage,
                                                foldin_env, monkeypatch):
        from predictionio_tpu.utils import faults, resilience
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        # bounded retries so the failing tail flips stale within the
        # test budget instead of burning the default 30s deadline
        monkeypatch.setenv("PIO_STORAGE_OP_DEADLINE", "0.2")
        _seed_app("recapp")
        _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            faults.install(
                "backend=memory,op=find_since,kind=error,rate=1,seed=4")
            deadline = time.time() + 10
            while time.time() < deadline and not srv._foldin.stale:
                time.sleep(0.05)
            assert srv._foldin.stale
            # serving continues from the last-good factors, stamped
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1"})
            assert status == 200 and result["itemScores"]
            assert result.get("degraded") is True
            assert "foldin_stale" in result["degradedReasons"]
            # tail recovery clears the flag and the stamp
            faults.clear()
            resilience.reset_breakers()
            deadline = time.time() + 10
            while time.time() < deadline and srv._foldin.stale:
                time.sleep(0.05)
            assert not srv._foldin.stale
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1"})
            assert status == 200
            assert "foldin_stale" not in result.get("degradedReasons", [])
        finally:
            faults.clear()
            resilience.reset_breakers()
            srv.stop()

    @pytest.mark.slow
    def test_default_cadence_freshness(self, mem_storage, monkeypatch):
        """The acceptance shape at the DEFAULT cadence (2s): a new
        user's first events are reflected in top-k well under 5s."""
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.setenv("PIO_FOLDIN", "1")
        monkeypatch.delenv("PIO_FOLDIN_INTERVAL", raising=False)
        aid = _seed_app("recapp")
        _train("recapp")
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            le = storage.get_levents()
            # warm the fold kernel with a throwaway user so the timed
            # probe measures cadence, not one-time jit
            le.insert(rate_event("warm", "i1", at=900), aid)
            self._wait_servable(srv.address, "warm")
            le.insert_batch([rate_event("fresh9", f"i{i}", val=5.0,
                                        at=1000 + i) for i in range(3)],
                            aid)
            took, _ = self._wait_servable(srv.address, "fresh9")
            assert took < 5.0
        finally:
            srv.stop()


class TestFoldInAttachValidation:
    def test_incompatible_engine_refused_at_deploy(self, mem_storage,
                                                   foldin_env):
        from predictionio_tpu.online.foldin import attach_foldin

        class NotALS:
            pass

        class Dep:
            models = [NotALS()]
            algorithms = [object()]

        with pytest.raises(ValueError, match="no deployed algorithm"):
            attach_foldin(Dep())


# ---------------------------------------------------------------------------
# Event-server observability satellite: the tail watermark in /stats.json
# ---------------------------------------------------------------------------

class TestEventServerTailWatermark:
    def test_stats_json_exposes_watermark(self, mem_storage):
        from predictionio_tpu.data.api import (
            EventServer,
            EventServerConfig,
        )

        aid = storage.get_metadata_apps().insert(App(0, "wmapp"))
        storage.get_metadata_access_keys().insert(
            AccessKey(key="wmkey", appid=aid))
        server = EventServer(EventServerConfig(
            ip="127.0.0.1", port=0, stats=True), reg=mem_storage).start()
        try:
            status, _ = _post(server.address, "/events.json",
                              rate_event("u1", "i1", at=1).to_dict(),
                              params={"accessKey": "wmkey"})
            assert status == 201
            status, data = _post(server.address, "/events.json",
                                 rate_event("u2", "i2", at=2).to_dict(),
                                 params={"accessKey": "wmkey"})
            assert status == 201
            last_id = data["eventId"]
            status, stats = _get(server.address,
                                 "/stats.json?accessKey=wmkey")
            assert status == 200
            wm = stats["tailWatermark"]
            assert wm["lastEventId"] == last_id
            assert wm["lastEventTime"]
            assert wm["cursor"]["kind"] == "memory"
        finally:
            server.stop()
