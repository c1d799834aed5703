"""Device-plane telemetry suite (PR 12): the per-dispatch flight
recorder (ring bounds, concurrency, kill switch), HBM accounting
(DeviceTopK.memory_report, AOTCache evictions/memory), the deployed
query server's /dispatches.json + /stats.json device block, the
profiler-capture single-flight endpoints, `pio top --once`, and the
recorder-on <5% serving-overhead gate."""

import datetime as dt
import json
import threading
import time
import urllib.parse

import http.client

import numpy as np
import pytest

from predictionio_tpu.controller import ComputeContext, EngineParams
from predictionio_tpu.data import storage
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.ops.aot import AOTCache
from predictionio_tpu.ops.als import ALSParams
from predictionio_tpu.ops.serving import (
    DeviceTopK,
    device_report,
)
from predictionio_tpu.templates.recommendation import (
    DataSourceParams,
    engine_factory,
)
from predictionio_tpu.utils import device_telemetry, metrics
from predictionio_tpu.utils.device_telemetry import FlightRecorder
from predictionio_tpu.workflow import QueryServer, ServerConfig, run_train
from predictionio_tpu.workflow.create_workflow import (
    WorkflowConfig,
    new_engine_instance,
)

UTC = dt.timezone.utc
CTX = ComputeContext()
FACTORY = "predictionio_tpu.templates.recommendation:engine_factory"


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Telemetry on + an empty ring for every test; restore after."""
    rec = device_telemetry.recorder()
    prior = rec.enabled
    rec.reset()
    rec.enabled = True
    yield rec
    rec.enabled = prior
    rec.reset()


def _record(rec, i=0, lane="users", device_us=100.0):
    rec.record({"ts": time.time(), "lane": lane, "kernel": "xla",
                "precision": "fp32", "aot": "hit", "kBucket": 16,
                "batch": 1 + i % 8, "bucket": 8, "fill": (1 + i % 8) / 8,
                "queueWaitUs": 10.0, "hostUs": device_us + 50.0,
                "deviceUs": device_us})


class TestFlightRecorder:
    def test_ring_eviction_bounds(self):
        rec = FlightRecorder(capacity=32, enabled=True)
        for i in range(100):
            _record(rec, i)
        counts = rec.counts()
        assert counts["recorded"] == 100
        assert counts["retained"] == 32
        assert counts["evicted"] == 68
        assert len(rec.snapshot(1000)) == 32
        assert rec.snapshot(0) == []  # summaries-only scrape shape
        # newest first
        snap = rec.snapshot(5)
        assert snap[0]["batch"] == 1 + 99 % 8

    def test_capacity_floor(self):
        assert FlightRecorder(capacity=1).capacity == 16

    def test_default_ring_holds_a_window(self, monkeypatch):
        # 51 s of the busiest session lane is about 2,400 dispatches,
        # of the user lane about 12,000
        monkeypatch.delenv("PIO_DEVICE_TELEMETRY_RING", raising=False)
        assert FlightRecorder().capacity == 16384
        monkeypatch.setenv("PIO_DEVICE_TELEMETRY_RING", "64")
        assert FlightRecorder().capacity == 64

    def test_kill_switch_fast_path(self, fresh_recorder):
        device_telemetry.set_enabled(False)
        assert not device_telemetry.enabled()
        assert device_telemetry.record_dispatch(
            lane="users", kernel="xla", precision="fp32", aot="hit",
            k_bucket=16, batch=1, bucket=8, host_us=1.0,
            device_us=1.0) is None
        assert fresh_recorder.counts()["recorded"] == 0
        device_telemetry.set_enabled(True)
        assert device_telemetry.record_dispatch(
            lane="users", kernel="xla", precision="fp32", aot="hit",
            k_bucket=16, batch=1, bucket=8, host_us=1.0,
            device_us=1.0) is not None
        assert fresh_recorder.counts()["recorded"] == 1

    def test_summary_shape(self):
        rec = FlightRecorder(capacity=64, enabled=True)
        for i in range(10):
            _record(rec, i, lane="users", device_us=100.0 + i)
        _record(rec, lane="foldin", device_us=500.0)
        s = rec.summary()
        assert set(s) == {"users", "foldin"}
        u = s["users"]
        assert u["dispatches"] == 10
        assert 100.0 <= u["deviceUsP50"] <= 109.0
        assert u["deviceUsP99"] >= u["deviceUsP50"]
        assert u["aot"] == {"hit": 10}
        assert u["meanFill"] is not None

    def test_summary_sums_the_stages_and_the_first_waits(self):
        rec = FlightRecorder(capacity=64, enabled=True)
        t0 = time.time()
        for i in range(5):
            rec.record({
                "ts": t0 + i, "lane": "sess", "aot": "hit",
                "deviceUs": 100.0, "hostUs": 150.0, "queueWaitUs": 900.0,
                "gapUs": 40.0, "gapIdleUs": 10.0, "gapWindowUs": 5.0,
                "pickUs": 4.0, "formUs": 6.0, "bookUs": 3.0,
                "lockWaitUs": 1.0, "otherUs": 2.0, "enqueueUs": 50.0,
                "fetchUs": 7.0, "deliverUs": 2.0,
                "lives": [{"firstWaitUs": 10.0 * (i + 1), "rounds": 1,
                           "ridingUs": 1.0, "betweenUs": 0.0}] * (i % 2)})
        _record(rec, lane="foldin")       # a record without stage stamps
        s = rec.summary()
        assert s["sess"]["stageUs"] == {
            "gapUs": 200.0, "gapIdleUs": 50.0, "gapWindowUs": 25.0,
            "pickUs": 20.0, "formUs": 30.0, "bookUs": 15.0,
            "lockWaitUs": 5.0, "otherUs": 10.0, "enqueueUs": 250.0,
            "deviceUs": 500.0, "fetchUs": 35.0, "deliverUs": 10.0}
        assert tuple(s["sess"]["stageUs"]) == device_telemetry.STAGE_FIELDS
        assert s["sess"]["spanSec"] == 4.0
        assert s["sess"]["queueWaitUsP50"] == 900.0
        assert s["sess"]["firstWaitUsP50"] in (20.0, 40.0)   # of 2 lives
        assert s["foldin"]["firstWaitUsP50"] is None
        assert s["foldin"]["stageUs"]["gapUs"] == 0.0
        assert s["foldin"]["stageUs"]["deviceUs"] == 100.0
        assert s["foldin"]["spanSec"] == 0.0

    def test_counts_carry_the_rings_lock_waits(self):
        rec = FlightRecorder(capacity=16, enabled=True)
        _record(rec)
        counts = rec.counts()
        assert counts["lockContended"] == 0
        assert counts["lockWaitedUs"] == 0.0
        held, release = threading.Event(), threading.Event()

        def holder():
            with rec._lock:
                held.set()
                release.wait(5.0)

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(5.0)
        threading.Timer(0.02, release.set).start()
        _record(rec)                        # waits for the holder
        t.join(timeout=5.0)
        assert not t.is_alive()
        counts = rec.counts()
        assert counts["lockContended"] == 1
        assert counts["lockWaitedUs"] >= 1_000
        assert counts["recorded"] == 2

    def test_concurrency_stress(self):
        """Dispatcher-style writers + scraper-style readers hammer the
        same ring; counts stay exact and no read ever explodes."""
        rec = FlightRecorder(capacity=128, enabled=True)
        N_WRITERS, N_EACH = 6, 300
        stop = threading.Event()
        errors = []

        def writer(wid):
            try:
                for i in range(N_EACH):
                    _record(rec, i, lane=f"lane{wid % 3}")
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    rec.snapshot(50)
                    rec.summary()
                    rec.counts()
            except Exception as e:  # pragma: no cover
                errors.append(e)

        readers = [threading.Thread(target=reader) for _ in range(3)]
        writers = [threading.Thread(target=writer, args=(w,))
                   for w in range(N_WRITERS)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert not errors
        counts = rec.counts()
        assert counts["recorded"] == N_WRITERS * N_EACH
        assert counts["retained"] == 128

    def test_report_is_json_safe(self):
        rec = FlightRecorder(capacity=32, enabled=True)
        _record(rec)
        json.dumps(rec.report(10))


class TestDispatchInstrumentation:
    def _store(self, microbatch=False, seen=True, n_users=24, n_items=16):
        rng = np.random.default_rng(3)
        X = rng.standard_normal((n_users, 8)).astype(np.float32)
        Y = rng.standard_normal((n_items, 8)).astype(np.float32)
        return DeviceTopK(X, Y,
                          seen={0: np.array([1, 2])} if seen else None,
                          microbatch=microbatch)

    def test_direct_dispatch_records(self, fresh_recorder):
        srv = self._store()
        srv.user_topk(0, 5)
        recs = fresh_recorder.snapshot(10)
        assert recs, "direct dispatch did not record"
        r = recs[0]
        assert r["lane"] == "user"
        assert r["kernel"] in ("xla", "fused")
        assert r["precision"] == "fp32"
        assert r["aot"] == "miss_jit"  # no warmup -> jit fallback
        assert r["kBucket"] == 16  # k=5 -> min bucket 16 (= n_items)
        assert r["deviceUs"] is not None and r["deviceUs"] >= 0
        assert r["hostUs"] >= r["deviceUs"]
        srv.close()

    def test_record_lies_on_the_span_clock(self, fresh_recorder):
        from predictionio_tpu.utils import tracing

        srv = self._store()
        before = tracing.span_now()
        with tracing.trace_scope("probe") as root:
            srv.users_topk(np.arange(4), 5)
        after = tracing.span_now()
        (r,) = fresh_recorder.snapshot(10)
        assert before <= r["calledTs"] < r["readyTs"] <= after
        assert (r["readyTs"] - r["calledTs"]) * 1e6 == \
            pytest.approx(r["hostUs"], abs=1.0)
        # `ts` is the wall clock when the record was written: at ready
        assert r["ts"] == pytest.approx(r["readyTs"], abs=0.05)
        spans = tracing.trace_buffer().get(root.trace_id)["spans"]
        (execute,) = [s for s in spans if s["name"] == "device.execute"]
        assert execute["start"] == r["calledTs"]
        assert execute["end"] == pytest.approx(r["readyTs"], abs=1e-6)
        srv.close()

    def test_aot_hit_after_warmup(self, fresh_recorder):
        srv = self._store()
        srv.warmup(max_k=16)
        fresh_recorder.reset()
        srv.user_topk(0, 5)
        srv.users_topk(np.arange(4), 5)
        recs = fresh_recorder.snapshot(10)
        assert {r["aot"] for r in recs} == {"hit"}
        lanes = {r["lane"] for r in recs}
        assert lanes == {"user", "users"}
        rep = srv.ladder_report()
        assert rep["requests"]["hit"] >= 2
        assert rep["coverage"]["planned"] > 0
        assert rep["coverage"]["planned"] == (
            rep["coverage"]["compiled"] + rep["coverage"]["fallback"])
        srv.close()

    def test_batched_lane_queue_wait_and_fill(self, fresh_recorder):
        srv = self._store(microbatch=True)
        srv.user_topk(0, 5)  # one batched round trip
        recs = [r for r in fresh_recorder.snapshot(10)
                if r["lane"] == "users"]
        assert recs
        r = recs[0]
        assert r["queueWaitUs"] is not None and r["queueWaitUs"] >= 0
        assert r["batch"] == 1 and r["bucket"] == 8
        assert r["fill"] == pytest.approx(1 / 8)
        srv.close()

    def test_metrics_fed(self, fresh_recorder, mem_storage):
        metrics.REGISTRY.reset()
        srv = self._store()
        srv.user_topk(0, 5)
        assert metrics.AOT_CACHE_REQUESTS.value(result="miss_jit") >= 1
        hist = metrics.DISPATCH_DEVICE_SECONDS.child(
            lane="user", kernel=srv._kernel, precision="fp32")
        assert hist.summary()["count"] >= 1
        srv.close()

    @pytest.mark.parametrize("lane", ["user", "users", "items"])
    def test_fused_dispatch_records_select_rounds(self, lane,
                                                  fresh_recorder,
                                                  monkeypatch):
        """The fused kernel's count of selection rounds rides in the
        packed result and lands on the record of its dispatch and in
        ``pio_topk_select_rounds_total``; the answers are the ones the
        XLA chain gives."""
        metrics.REGISTRY.reset()
        xla = self._store(n_items=100)
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        srv = self._store(n_items=100)
        assert srv._kernel == "fused" and xla._kernel == "xla"
        call = {"user": lambda s: s.user_topk(0, 5),
                "users": lambda s: s.users_topk(np.arange(6), 5),
                "items": lambda s: s.items_topk([3, 4], 5)}[lane]
        want = call(xla)
        assert "selectRounds" not in fresh_recorder.snapshot(1)[0]
        got = call(srv)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
        rec = fresh_recorder.snapshot(1)[0]
        assert rec["lane"] == lane and rec["kernel"] == "fused"
        # 100 items are one tile, and the empty list takes its best 16
        assert rec["selectRounds"] == 16
        assert metrics.TOPK_SELECT_ROUNDS.value(lane=lane) \
            == rec["selectRounds"]
        xla.close()
        srv.close()

    def test_select_rounds_off_with_the_recorder(self, fresh_recorder,
                                                 monkeypatch):
        metrics.REGISTRY.reset()
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        device_telemetry.set_enabled(False)
        srv = self._store(n_items=200)
        idx, _ = srv.user_topk(0, 5)
        assert len(idx) == 5
        assert fresh_recorder.counts()["recorded"] == 0
        assert metrics.TOPK_SELECT_ROUNDS.value(lane="user") == 0
        srv.close()

    def test_killed_lane_still_serves(self, fresh_recorder):
        device_telemetry.set_enabled(False)
        srv = self._store()
        idx, scores = srv.user_topk(0, 5)
        assert len(idx) > 0
        assert fresh_recorder.counts()["recorded"] == 0
        srv.close()

    def test_foldin_solve_records(self, fresh_recorder):
        from predictionio_tpu.ops.als import fold_in_users

        Y = np.random.default_rng(0).standard_normal(
            (16, 8)).astype(np.float32)
        rows = fold_in_users(Y, [np.array([0, 1, 2])],
                             [np.array([4.0, 5.0, 3.0])],
                             ALSParams(rank=8))
        assert rows.shape == (1, 8)
        recs = [r for r in fresh_recorder.snapshot(10)
                if r["lane"] == "foldin"]
        assert recs and recs[0]["aot"] == "jit"
        assert recs[0]["batch"] == 1


class TestMemoryReport:
    def test_fp32_component_bytes(self):
        X = np.zeros((20, 8), dtype=np.float32)
        Y = np.zeros((16, 8), dtype=np.float32)
        srv = DeviceTopK(X, Y, seen={0: np.array([1])}, microbatch=False)
        rep = srv.memory_report()
        assert rep["components"]["userFactors"]["bytes"] == 20 * 8 * 4
        assert rep["components"]["itemFactors"]["bytes"] == 16 * 8 * 4
        assert rep["components"]["userFactors"]["dtype"] == "float32"
        seen = rep["components"]["seen"]
        assert seen["bytes"] > 0
        assert rep["totalBytes"] == sum(
            c["bytes"] + c.get("scaleBytes", 0)
            for c in rep["components"].values() if c is not None)
        srv.close()

    def test_int8_store_splits_scales(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        X = np.random.default_rng(0).standard_normal(
            (20, 8)).astype(np.float32)
        Y = np.random.default_rng(1).standard_normal(
            (16, 8)).astype(np.float32)
        srv = DeviceTopK(X, Y, microbatch=False)
        rep = srv.memory_report()
        uf = rep["components"]["userFactors"]
        assert uf["dtype"] == "int8"
        assert uf["bytes"] == 20 * 8  # one byte per element
        assert uf["scaleBytes"] == 20 * 4  # fp32 per-row scales
        assert rep["precision"] == "int8"
        srv.close()

    def test_report_tracks_foldin_growth(self):
        X = np.zeros((16, 8), dtype=np.float32)
        Y = np.zeros((16, 8), dtype=np.float32)
        srv = DeviceTopK(X, Y, microbatch=False)
        before = srv.memory_report()
        srv.patch_users([20], np.ones((1, 8), dtype=np.float32))
        after = srv.memory_report()
        assert after["userCapacity"] > before["userCapacity"]
        assert after["components"]["userFactors"]["bytes"] > \
            before["components"]["userFactors"]["bytes"]
        srv.close()

    def test_device_report_aggregates(self):
        X = np.zeros((16, 8), dtype=np.float32)
        Y = np.zeros((16, 8), dtype=np.float32)
        srv = DeviceTopK(X, Y, microbatch=False)
        rep = device_report()
        assert rep["storeBytes"] >= srv.memory_report()["totalBytes"]
        assert "dispatch" in rep and "telemetry" in rep
        json.dumps(rep)
        srv.close()


class TestAOTCacheObservability:
    def test_eviction_counted_and_metered(self, mem_storage):
        metrics.REGISTRY.reset()
        cache = AOTCache(max_entries=2, name="test-cache")
        for i in range(4):
            cache.put(("sig", i), object())
        assert len(cache) == 2
        assert cache.evictions == 2
        assert cache.stats() == {"entries": 2, "maxEntries": 2,
                                 "evictions": 2}
        assert metrics.AOT_CACHE_EVICTIONS.value() == 2

    def test_eviction_logs_dropped_signature(self, caplog):
        import logging

        cache = AOTCache(max_entries=1, name="test-cache")
        cache.put(("old-sig",), object())
        with caplog.at_level(logging.WARNING, logger="pio.aot"):
            cache.put(("new-sig",), object())
        assert any("old-sig" in r.message for r in caplog.records)

    def test_memory_report_best_effort(self):
        cache = AOTCache(max_entries=4)

        class NoStats:
            def memory_analysis(self):
                raise RuntimeError("no stats here")

        cache.put("a", NoStats())
        rep = cache.memory_report()
        assert rep == {"entries": 1, "entriesAnalyzed": 0,
                       "tempBytes": 0, "codeBytes": 0, "totalBytes": 0}

    def test_memory_report_real_executable(self):
        import jax

        cache = AOTCache(max_entries=4)
        fn = jax.jit(lambda x: x * 2)
        compiled = fn.lower(np.zeros((8,), np.float32)).compile()
        cache.put("prog", compiled)
        rep = cache.memory_report()
        assert rep["entries"] == 1
        # CPU jaxlib provides memory_analysis; if a future version
        # drops it the report must degrade to zero, not explode
        assert rep["totalBytes"] >= 0


# ---------------------------------------------------------------------------
# Deployed-server surfaces
# ---------------------------------------------------------------------------


def seed_and_train(app_name="telapp"):
    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    t0 = dt.datetime(2021, 1, 1, tzinfo=UTC)
    le.insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item",
              target_entity_id=f"i{rng.integers(0, 10)}",
              properties={"rating": float(rng.integers(3, 6))},
              event_time=t0)
        for u in range(16) for _ in range(6)], aid)
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name=app_name)),
        algorithm_params_list=[
            ("als", ALSParams(rank=8, num_iterations=2, seed=0))])
    iid = run_train(engine_factory(), params,
                    new_engine_instance(
                        WorkflowConfig(engine_factory=FACTORY), params),
                    ctx=CTX)
    assert iid is not None
    return iid


@pytest.fixture
def deployed(mem_storage, monkeypatch):
    # the device block under test needs the DEVICE serving path
    monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
    seed_and_train()
    srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
        undeploy_stale=False)
    yield srv
    srv.stop()


def request(addr, method, path, body=None, params=None):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    target = path + ("?" + urllib.parse.urlencode(params)
                     if params else "")
    conn.request(method, target,
                 body=None if body is None else json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data) if data else None


def scrape_metrics(addr):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode("utf-8")
    conn.close()
    return text


class TestDeployedSurfaces:
    def _drive(self, addr, n=6):
        for u in range(n):
            status, _ = request(addr, "POST", "/queries.json",
                                {"user": f"u{u}", "num": 3})
            assert status == 200

    def test_dispatches_json_schema(self, deployed):
        self._drive(deployed.address)
        status, payload = request(deployed.address, "GET",
                                  "/dispatches.json")
        assert status == 200
        assert payload["enabled"] is True
        for key in ("recorded", "retained", "evicted", "capacity",
                    "lockContended", "lockWaitedUs", "summary",
                    "dispatches"):
            assert key in payload
        assert payload["recorded"] > 0
        rec = payload["dispatches"][0]
        for key in ("ts", "lane", "kernel", "precision", "aot",
                    "kBucket", "batch", "bucket", "fill", "queueWaitUs",
                    "hostUs", "deviceUs"):
            assert key in rec, key
        assert rec["aot"] in ("hit", "miss_jit", "jit")
        lane = payload["summary"]["users"]
        assert lane["dispatches"] > 0
        assert lane["deviceUsP50"] is not None
        # the operator's view of the dispatcher's tiling
        assert lane["firstWaitUsP50"] is not None
        assert lane["stageUs"]["gapUs"] >= lane["stageUs"]["otherUs"] >= 0
        assert lane["stageUs"]["enqueueUs"] > 0 and lane["spanSec"] >= 0
        _, stats = request(deployed.address, "GET", "/stats.json")
        assert set(stats["stages"]["lock"]) == {"contended", "waitedUs"}

    def test_dispatches_json_limit(self, deployed):
        self._drive(deployed.address)
        status, payload = request(deployed.address, "GET",
                                  "/dispatches.json",
                                  params={"limit": 2})
        assert status == 200 and len(payload["dispatches"]) <= 2
        status, payload = request(deployed.address, "GET",
                                  "/dispatches.json",
                                  params={"limit": "bogus"})
        assert status == 200  # malformed limit falls back, never 500s

    def test_stats_json_device_block(self, deployed):
        self._drive(deployed.address)
        status, payload = request(deployed.address, "GET", "/stats.json")
        assert status == 200
        dev = payload["device"]
        assert dev["telemetry"]["enabled"] is True
        assert dev["storeBytes"] > 0
        assert len(dev["stores"]) >= 1
        store = dev["stores"][0]["store"]
        assert store["precision"] in ("fp32", "bf16", "int8")
        assert store["components"]["userFactors"]["bytes"] > 0
        ladder = dev["stores"][0]["aotLadder"]
        cov = ladder["coverage"]
        assert cov["planned"] > 0
        assert cov["planned"] == cov["compiled"] + cov["fallback"]
        assert ladder["requests"]["hit"] >= 0
        assert "evictions" in ladder["cache"]
        assert dev["dispatch"]["users"]["dispatches"] > 0

    def test_device_gauges_exposed(self, deployed):
        self._drive(deployed.address, n=2)
        text = scrape_metrics(deployed.address)
        store_line = next(ln for ln in text.splitlines()
                          if ln.startswith("pio_device_store_bytes"))
        assert float(store_line.split()[-1]) > 0
        assert "pio_aot_cache_requests_total" in text
        assert "pio_dispatch_device_seconds_bucket" in text

    def test_select_rounds_on_dispatches_and_metrics(self, mem_storage,
                                                     monkeypatch):
        """A deploy on the fused kernel: ``selectRounds`` on the
        ``/dispatches.json`` records and the counter on ``/metrics``."""
        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        seed_and_train()
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            self._drive(srv.address, n=3)
            _, payload = request(srv.address, "GET", "/dispatches.json")
            recs = [r for r in payload["dispatches"]
                    if r["lane"] == "users"]
            assert recs and all(r["kernel"] == "fused" and
                                r["selectRounds"] > 0 for r in recs)
            line = next(ln for ln in
                        scrape_metrics(srv.address).splitlines()
                        if ln.startswith(
                            'pio_topk_select_rounds_total{lane="users"}'))
            assert float(line.split()[-1]) >= sum(
                r["selectRounds"] for r in recs)
        finally:
            srv.stop()

    def test_slow_query_log_carries_dispatch_context(
            self, deployed, monkeypatch):
        from predictionio_tpu.utils import tracing

        buf = tracing.trace_buffer()
        prior = buf.slow_threshold_sec
        buf.slow_threshold_sec = 0.0  # every query is "slow"
        try:
            self._drive(deployed.address, n=2)
            entries = buf.slow_log(10)
        finally:
            buf.slow_threshold_sec = prior
        with_ctx = [e for e in entries if "dispatch" in e]
        assert with_ctx, f"no dispatch context in slow log: {entries}"
        d = with_ctx[0]["dispatch"]
        for key in ("lane", "kernel", "aot", "bucket", "batch", "fill"):
            assert key in d, key

    def test_pio_top_once(self, deployed, capsys):
        from predictionio_tpu.tools.cli import main

        self._drive(deployed.address, n=3)
        host, port = deployed.address
        rc = main(["top", "--url", f"http://{host}:{port}", "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "pio top" in out
        assert "oldest age p50" in out and "first wait p50" in out
        assert "device" in out and "HBM store" in out
        assert "queries" in out
        assert "\x1b[" not in out  # --once is plain text (scripts/CI)

    def test_pio_top_unreachable(self, capsys):
        from predictionio_tpu.tools.cli import main

        rc = main(["top", "--url", "http://127.0.0.1:1", "--once"])
        assert rc == 1

    def test_dispatches_json_kill_switch(self, deployed):
        device_telemetry.set_enabled(False)
        try:
            device_telemetry.recorder().reset()
            self._drive(deployed.address, n=2)
            status, payload = request(deployed.address, "GET",
                                      "/dispatches.json")
            assert status == 200
            assert payload["enabled"] is False
            assert payload["recorded"] == 0
        finally:
            device_telemetry.set_enabled(True)


class TestProfilerCapture:
    def test_single_flight_and_stop(self, deployed, tmp_path,
                                    monkeypatch):
        monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path))
        addr = deployed.address
        status, r = request(addr, "POST", "/profile/start")
        assert status == 200 and r["profileDir"].startswith(str(tmp_path))
        # single-flight: a second start while one runs is 409
        status2, r2 = request(addr, "POST", "/profile/start")
        assert status2 == 409
        assert "already running" in r2["message"]
        # some device work lands in the capture
        request(addr, "POST", "/queries.json", {"user": "u1", "num": 3})
        status3, r3 = request(addr, "POST", "/profile/stop")
        assert status3 == 200
        assert r3["durationSec"] >= 0
        import os

        assert os.path.isdir(r3["profileDir"])
        # stop with nothing running is 409, and a fresh start works
        status4, _ = request(addr, "POST", "/profile/stop")
        assert status4 == 409
        status5, _ = request(addr, "POST", "/profile/start")
        assert status5 == 200
        status6, _ = request(addr, "POST", "/profile/stop")
        assert status6 == 200

    def test_capture_lands_next_to_trace_dir(self, mem_storage, tmp_path,
                                             monkeypatch):
        from predictionio_tpu.utils import tracing
        from predictionio_tpu.utils.tracing import PROFILER

        monkeypatch.delenv("PIO_PROFILE_DIR", raising=False)
        tracing.set_trace_dir(str(tmp_path / "traces"))
        try:
            assert PROFILER.resolve_base_dir() == str(
                tmp_path / "traces" / "profiles")
        finally:
            tracing.set_trace_dir(None)

    def test_authed_when_server_json_has_key(self, mem_storage, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        cfg_path = tmp_path / "server.json"
        cfg_path.write_text(json.dumps({"accessKey": "s3cret"}))
        seed_and_train(app_name="authapp")
        srv = QueryServer(ServerConfig(
            ip="127.0.0.1", port=0,
            server_config_path=str(cfg_path))).start(undeploy_stale=False)
        try:
            addr = srv.address
            status, _ = request(addr, "POST", "/profile/start")
            assert status == 403
            status, _ = request(addr, "POST", "/profile/start",
                                params={"accessKey": "wrong"})
            assert status == 403
            monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path / "prof"))
            status, _ = request(addr, "POST", "/profile/start",
                                params={"accessKey": "s3cret"})
            assert status == 200
            status, _ = request(addr, "POST", "/profile/stop",
                                params={"accessKey": "s3cret"})
            assert status == 200
        finally:
            srv.stop()


class TestOverheadGate:
    @pytest.mark.perf
    @pytest.mark.slow
    def test_recorder_overhead_under_5_percent(self, deployed):
        """The acceptance gate (mirroring the PR-2 metrics overhead
        rule): served-query p50 with the flight recorder ON must be
        within 5% of the PIO_DEVICE_TELEMETRY=0 killed lane, and the
        zero-steady-state-compile assertion stays green with the
        recorder on (the timing wrapper must never change program
        identity)."""
        host, port = deployed.address
        N = 120
        metrics.install_jit_compile_listener()
        body = json.dumps({"user": "u1", "num": 3})

        def one_round():
            conn = http.client.HTTPConnection(host, port, timeout=30)
            samples = []
            for _ in range(N):
                t0 = time.perf_counter()
                conn.request("POST", "/queries.json", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 200
                samples.append(time.perf_counter() - t0)
            conn.close()
            return float(np.percentile(np.asarray(samples), 50))

        one_round()  # warm
        compiles0 = metrics.JIT_COMPILES.value()
        device_telemetry.set_enabled(True)
        p50_on = min(one_round() for _ in range(3))
        device_telemetry.set_enabled(False)
        p50_off = min(one_round() for _ in range(3))
        device_telemetry.set_enabled(True)
        assert metrics.JIT_COMPILES.value() == compiles0, \
            "telemetry introduced a steady-state compile"
        overhead = p50_on / p50_off - 1.0
        assert overhead < 0.05, (p50_on, p50_off, overhead)
