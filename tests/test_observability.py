"""End-to-end observability: GET /metrics on both servers (valid
Prometheus text, counter monotonicity, cumulative buckets), the richer
/stats.json views, X-Request-ID round-trip + propagation into storage-op
records, storage-op metrics across all four event backends, the
materialized-aggregation counters, and the metrics-on serving overhead
gate (< 5%, perf-marked)."""

import datetime as dt
import http.client
import json
import logging
import math
import re
import time
import urllib.parse

import pytest

from predictionio_tpu.data import storage as storage_mod
from predictionio_tpu.data.api.event_server import (
    EventServer,
    EventServerConfig,
)
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import AccessKey, App
from predictionio_tpu.utils import metrics

from test_metrics import parse_prometheus

UTC = dt.timezone.utc
APP_ID = 9
KEY = "obskey"


@pytest.fixture
def event_server(mem_storage):
    mem_storage.get_metadata_apps().insert(App(id=APP_ID, name="obsapp"))
    mem_storage.get_metadata_access_keys().insert(
        AccessKey(key=KEY, appid=APP_ID))
    srv = EventServer(EventServerConfig(ip="127.0.0.1", port=0, stats=True),
                      reg=mem_storage)
    srv.start()
    yield srv
    srv.stop()


def raw_request(addr, method, path, body=None, headers=None):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    payload = None
    hdrs = dict(headers or {})
    if body is not None:
        payload = body if isinstance(body, (bytes, str)) else json.dumps(body)
        hdrs.setdefault("Content-Type", "application/json")
    conn.request(method, path, body=payload, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    out_headers = dict(resp.getheaders())
    conn.close()
    return resp.status, data, out_headers


def scrape(addr):
    status, data, headers = raw_request(addr, "GET", "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    return parse_prometheus(data.decode("utf-8"))


RATE = {"event": "rate", "entityType": "user", "entityId": "u1",
        "targetEntityType": "item", "targetEntityId": "i1",
        "properties": {"rating": 4.0}}


class TestEventServerMetrics:
    def test_metrics_endpoint_exposition(self, event_server):
        addr = event_server.address
        q = f"/events.json?accessKey={KEY}"
        for _ in range(3):
            status, _, _ = raw_request(addr, "POST", q, body=RATE)
            assert status == 201
        samples, types = scrape(addr)
        assert types["pio_http_requests_total"] == "counter"
        assert types["pio_http_request_seconds"] == "histogram"
        assert types["pio_ingest_events_total"] == "counter"
        # per-route request counter (route pattern, not raw path)
        assert samples[("pio_http_requests_total",
                        (("method", "POST"), ("route", "/events.json"),
                         ("server", "event"), ("status", "201")))] >= 3
        # per-event-type ingest counter
        assert samples[("pio_ingest_events_total",
                        (("app_id", str(APP_ID)), ("event", "rate"),
                         ("status", "201")))] >= 3
        # storage-op latency for the backing store rode along (shard is
        # empty for direct, non-fleet DAOs)
        assert samples[("pio_storage_op_seconds_count",
                        (("backend", "memory"), ("op", "insert"),
                         ("shard", "")))] >= 3

    def test_counter_monotonic_and_buckets_cumulative(self, event_server):
        addr = event_server.address
        key = ("pio_http_requests_total",
               (("method", "POST"), ("route", "/events.json"),
                ("server", "event"), ("status", "201")))

        def settled_scrape():
            # the status-labeled counter increments AFTER the response
            # bytes are on the wire (the dispatch shell's finally), so
            # an immediate scrape can race an in-flight increment —
            # poll until the counter is quiescent across two scrapes
            end = time.monotonic() + 5.0
            s, _ = scrape(addr)
            while time.monotonic() < end:
                time.sleep(0.02)
                s2, _ = scrape(addr)
                if s2.get(key, 0) == s.get(key, 0):
                    return s2
                s = s2
            return s

        raw_request(addr, "POST", f"/events.json?accessKey={KEY}", body=RATE)
        s1 = settled_scrape()
        raw_request(addr, "POST", f"/events.json?accessKey={KEY}", body=RATE)
        s2 = settled_scrape()
        assert s2[key] == s1[key] + 1
        # cumulative le buckets: monotone, +Inf equals _count
        hkey = (("route", "/events.json"), ("server", "event"))
        buckets = sorted(
            ((dict(k[1])["le"], v) for k, v in s2.items()
             if k[0] == "pio_http_request_seconds_bucket"
             and tuple(sorted(
                 (p for p in k[1] if p[0] != "le"))) == hkey),
            key=lambda p: math.inf if p[0] == "+Inf" else float(p[0]))
        counts = [v for _, v in buckets]
        assert counts and counts == sorted(counts)
        assert counts[-1] == s2[("pio_http_request_seconds_count", hkey)]

    def test_metrics_unauthenticated(self, event_server):
        status, _, _ = raw_request(event_server.address, "GET", "/metrics")
        assert status == 200

    def test_stats_json_carries_registry_snapshot(self, event_server):
        raw_request(event_server.address, "POST",
                    f"/events.json?accessKey={KEY}", body=RATE)
        status, data, _ = raw_request(
            event_server.address, "GET", f"/stats.json?accessKey={KEY}")
        assert status == 200
        payload = json.loads(data)
        assert "longLive" in payload  # parity shape intact
        assert "pio_http_requests_total" in payload["metrics"]
        assert "pio_ingest_events_total" in payload["metrics"]

    def test_stats_json_scoped_to_authed_app(self, event_server,
                                             mem_storage):
        """/stats.json is app-scoped in the reference; the registry
        snapshot riding along must not widen it to other tenants'
        ingest series."""
        other = 31
        mem_storage.get_metadata_apps().insert(App(id=other, name="tenant2"))
        mem_storage.get_metadata_access_keys().insert(
            AccessKey(key="otherkey", appid=other))
        addr = event_server.address
        secret = dict(RATE, event="secret-campaign")
        raw_request(addr, "POST", "/events.json?accessKey=otherkey",
                    body=secret)
        raw_request(addr, "POST", f"/events.json?accessKey={KEY}",
                    body=RATE)
        status, data, _ = raw_request(
            addr, "GET", f"/stats.json?accessKey={KEY}")
        assert status == 200
        ingest = json.loads(data)["metrics"]["pio_ingest_events_total"]
        apps = {s["labels"]["app_id"] for s in ingest["series"]}
        assert apps == {str(APP_ID)}
        assert not any(s["labels"]["event"] == "secret-campaign"
                       for s in ingest["series"])

    def test_ingest_event_label_cardinality_capped(self, event_server):
        """A client inventing unbounded event names must not mint
        unbounded registry series."""
        cap = event_server._event_label._cap
        addr = event_server.address

        def event_labels():
            samples, _ = scrape(addr)
            return {dict(k[1])["event"] for k in samples
                    if k[0] == "pio_ingest_events_total"}

        before = event_labels()  # series minted by earlier tests persist
        for i in range(cap + 20):
            body = dict(RATE, event=f"spam-{i}")
            status, _, _ = raw_request(
                addr, "POST", f"/events.json?accessKey={KEY}", body=body)
            assert status == 201
        minted = event_labels() - before
        assert len(minted) <= cap + 1  # this server's names + "<other>"
        assert "<other>" in minted or "<other>" in before
        assert "spam-119" not in minted | before  # past-cap name collapsed

    def test_raw_path_does_not_mint_series(self, event_server):
        addr = event_server.address
        raw_request(addr, "GET", f"/events/ev-123.json?accessKey={KEY}")
        raw_request(addr, "GET", "/totally/made/up")
        # a handler counts its request after the response is written, on
        # its own thread: on a loaded machine the scrape can get there
        # first, so give the count a moment
        deadline = time.monotonic() + 5.0
        while True:
            samples, _ = scrape(addr)
            routes = {dict(k[1]).get("route") for k in samples
                      if k[0] == "pio_http_requests_total"}
            if "<other>" in routes or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert "/events/<id>.json" in routes
        assert "<other>" in routes
        assert not any(r and "ev-123" in r for r in routes)


class TestRequestId:
    def test_round_trip_given_id(self, event_server):
        _, _, headers = raw_request(
            event_server.address, "GET", "/",
            headers={"X-Request-ID": "client-id-42"})
        assert headers["X-Request-ID"] == "client-id-42"

    def test_generated_when_absent(self, event_server):
        _, _, h1 = raw_request(event_server.address, "GET", "/")
        _, _, h2 = raw_request(event_server.address, "GET", "/")
        assert re.fullmatch(r"[0-9a-f]{16}", h1["X-Request-ID"])
        assert h1["X-Request-ID"] != h2["X-Request-ID"]

    def test_hostile_id_replaced(self, event_server):
        evil = 'x" onmouseover="\r\nSet-Cookie: a=b'
        _, _, headers = raw_request(
            event_server.address, "GET", "/",
            headers={"X-Request-ID": evil.replace("\r", "").replace(
                "\n", "")})
        assert headers["X-Request-ID"] != evil
        assert re.fullmatch(r"[0-9a-f]{16}", headers["X-Request-ID"])

    def test_propagates_into_storage_op_records(self, event_server,
                                                caplog):
        with caplog.at_level(logging.DEBUG, logger="pio.storage.ops"):
            status, _, _ = raw_request(
                event_server.address, "POST",
                f"/events.json?accessKey={KEY}", body=RATE,
                headers={"X-Request-ID": "trace-me-77"})
            assert status == 201
        records = [r.message for r in caplog.records
                   if "rid=trace-me-77" in r.message]
        assert any("memory.insert" in m for m in records)


class TestFourBackendStorageMetrics:
    def _exercise(self, reg):
        le = reg.get_levents()
        le.init(1)
        le.insert(Event(event="$set", entity_type="user", entity_id="e1",
                        properties={"a": 1},
                        event_time=dt.datetime(2021, 1, 1, tzinfo=UTC)), 1)
        assert len(list(le.find(app_id=1, limit=-1))) == 1
        assert "e1" in le.aggregate_properties(1, "user")

    def test_all_four_event_backends_report(self, tmp_path):
        """memory, sqlite, jsonlfs and resthttp all surface
        pio_storage_op_seconds{backend=...} through the registry-wrapped
        DAOs (resthttp against a live jsonlfs-backed event server)."""
        from predictionio_tpu.data.storage.sqlite import SqliteClient

        def reg_for(typ, **cfg):
            return storage_mod.StorageRegistry(storage_mod.StorageConfig(
                sources={"EV": {"type": typ, **cfg},
                         "META": {"type": "memory"}},
                repositories={"EVENTDATA": "EV", "METADATA": "META",
                              "MODELDATA": "META"}))

        self._exercise(reg_for("memory"))
        self._exercise(reg_for("sqlite", path=str(tmp_path / "m.db")))
        self._exercise(reg_for("jsonlfs", path=str(tmp_path / "ev")))
        server_reg = storage_mod.StorageRegistry(storage_mod.StorageConfig(
            sources={"EV": {"type": "jsonlfs",
                            "path": str(tmp_path / "srv_ev")},
                     "META": {"type": "memory"}},
            repositories={"EVENTDATA": "EV", "METADATA": "META",
                          "MODELDATA": "META"}))
        server = EventServer(
            EventServerConfig(ip="127.0.0.1", port=0,
                              service_key="obs-secret"),
            reg=server_reg).start()
        try:
            host, port = server.address
            self._exercise(reg_for(
                "resthttp", url=f"http://{host}:{port}",
                service_key="obs-secret"))
            samples, _ = parse_prometheus(
                metrics.registry().render_prometheus())
            backends = {dict(k[1]).get("backend") for k in samples
                        if k[0] == "pio_storage_op_seconds_count"}
            assert {"memory", "sqlite", "jsonlfs",
                    "resthttp"} <= backends
        finally:
            server.stop()
            SqliteClient.shutdown_all()


class TestAggregationCounters:
    def test_hit_replay_backfill_drop(self, tmp_path):
        from predictionio_tpu.data.storage.sqlite import (
            SqliteClient, SqliteLEvents,
        )

        le = SqliteLEvents({"path": str(tmp_path / "agg.db")})
        try:
            le.insert(Event(event="$set", entity_type="user",
                            entity_id="e1", properties={"a": 1},
                            event_time=dt.datetime(2021, 1, 1,
                                                   tzinfo=UTC)), 1)
            hits0 = metrics.AGGREGATE_HITS.value(backend="sqlite")
            backfills0 = metrics.AGGREGATE_BACKFILLS.value(backend="sqlite")
            drops0 = metrics.AGGREGATE_SCOPE_DROPS.value(backend="sqlite")
            bounded0 = metrics.AGGREGATE_REPLAYS.value(backend="sqlite",
                                                       reason="bounded")
            # first unbounded read: backfill + hit; second: hit only
            le.aggregate_properties(1, "user")
            le.aggregate_properties(1, "user")
            assert metrics.AGGREGATE_HITS.value(
                backend="sqlite") == hits0 + 2
            assert metrics.AGGREGATE_BACKFILLS.value(
                backend="sqlite") == backfills0 + 1
            # bounded read replays
            le.aggregate_properties(
                1, "user",
                until_time=dt.datetime(2022, 1, 1, tzinfo=UTC))
            assert metrics.AGGREGATE_REPLAYS.value(
                backend="sqlite", reason="bounded") == bounded0 + 1
            # bulk cutoff drops the materialized scope
            le.delete_until(1, dt.datetime(2022, 1, 1, tzinfo=UTC))
            assert metrics.AGGREGATE_SCOPE_DROPS.value(
                backend="sqlite") > drops0
        finally:
            SqliteClient.shutdown_all()

    def test_fallback_counted_for_stateless_backend(self):
        from predictionio_tpu.data.storage.base import LEvents

        class Bare(LEvents):
            metrics_backend = "baretest"

            def init(self, app_id, channel_id=None):
                return True

            def remove(self, app_id, channel_id=None):
                return True

            def close(self):
                pass

            def insert(self, event, app_id, channel_id=None):
                return "x"

            def get(self, event_id, app_id, channel_id=None):
                return None

            def delete(self, event_id, app_id, channel_id=None):
                return False

            def find(self, app_id, channel_id=None, **kw):
                return iter(())

        before = metrics.AGGREGATE_REPLAYS.value(backend="baretest",
                                                 reason="fallback")
        Bare().aggregate_properties(1, "user")
        assert metrics.AGGREGATE_REPLAYS.value(
            backend="baretest", reason="fallback") == before + 1


class TestQueryServerMetrics:
    @pytest.fixture
    def qserver(self, mem_storage):
        from test_query_server import seed_ratings, train_once
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        seed_ratings()
        train_once()
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        yield srv
        srv.stop()

    def _query(self, addr, body, headers=None):
        return raw_request(addr, "POST", "/queries.json", body=body,
                           headers=headers)

    def test_metrics_and_stats_json(self, qserver):
        addr = qserver.address
        for user in ("u1", "u2"):
            status, _, _ = self._query(addr, {"user": user, "num": 2})
            assert status == 200
        samples, types = scrape(addr)
        assert types["pio_query_seconds"] == "histogram"
        qkey = ("pio_query_seconds_count", (("variant", "engine.json"),))
        assert samples[qkey] >= 2
        assert samples[("pio_http_requests_total",
                        (("method", "POST"), ("route", "/queries.json"),
                         ("server", "query"), ("status", "200")))] >= 2

        status, data, _ = raw_request(addr, "GET", "/stats.json")
        assert status == 200
        payload = json.loads(data)
        assert payload["status"] == "alive"
        snap = payload["metrics"]
        # differential at the endpoint level: the JSON snapshot agrees
        # with the Prometheus scrape of the same server
        samples2, _ = scrape(addr)
        series = snap["pio_query_seconds"]["series"]
        mine = next(s for s in series
                    if s["labels"] == {"variant": "engine.json"})
        assert samples2[qkey] == mine["count"]
        for b in mine["buckets"]:
            bkey = (("le", b["le"]), ("variant", "engine.json"))
            assert samples2[("pio_query_seconds_bucket",
                             bkey)] == b["cumulative"]

    def test_request_id_round_trip(self, qserver):
        status, _, headers = self._query(
            qserver.address, {"user": "u1"},
            headers={"X-Request-ID": "query-rid-9"})
        assert status == 200
        assert headers["X-Request-ID"] == "query-rid-9"
        _, _, h2 = raw_request(qserver.address, "GET", "/")
        assert re.fullmatch(r"[0-9a-f]{16}", h2["X-Request-ID"])

    @pytest.mark.perf
    @pytest.mark.slow
    def test_metrics_overhead_under_5_percent(self, qserver):
        """Perf-only (run with ``-m perf``): serving QPS with the
        registry enabled must be within 5% of disabled — observability
        can never silently tax the hot path. Excluded from tier-1 (HTTP
        wall-clock flakes under parallel CI load)."""
        addr = qserver.address
        N = 150

        def one_round():
            host, port = addr
            conn = http.client.HTTPConnection(host, port, timeout=30)
            body = json.dumps({"user": "u1", "num": 3})
            t0 = time.perf_counter()
            for _ in range(N):
                conn.request("POST", "/queries.json", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 200
            took = time.perf_counter() - t0
            conn.close()
            return took

        prior = metrics.REGISTRY.enabled
        try:
            one_round()  # warm
            t_on = min(metrics.set_enabled(True) or one_round()
                       for _ in range(3))
            t_off = min(metrics.set_enabled(False) or one_round()
                        for _ in range(3))
        finally:
            metrics.set_enabled(prior)
        overhead = t_on / t_off - 1.0
        assert overhead < 0.05, (t_on, t_off, overhead)


class TestCliWiring:
    def test_train_profile_dir_env(self, mem_storage, tmp_path,
                                   monkeypatch, capsys):
        """$PIO_PROFILE_DIR (no flag) captures a jax.profiler trace of
        the train pass — profile_trace no longer sits unused outside
        tests."""
        import numpy as np

        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.tools.cli import main

        aid = storage_mod.get_metadata_apps().insert(App(0, "profapp"))
        le = storage_mod.get_levents()
        le.init(aid)
        rng = np.random.default_rng(1)
        t0 = dt.datetime(2021, 1, 1, tzinfo=UTC)
        le.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, 6)}",
                  properties={"rating": float(rng.integers(1, 6))},
                  event_time=t0)
            for u in range(12) for _ in range(5)], aid)

        engine_dir = tmp_path / "profengine"
        assert main(["template", "get", "recommendation",
                     str(engine_dir)]) == 0
        variant_path = engine_dir / "engine.json"
        variant = json.loads(variant_path.read_text())
        variant["datasource"]["params"]["appName"] = "profapp"
        variant["algorithms"][0]["params"].update(
            {"rank": 4, "numIterations": 2})
        variant_path.write_text(json.dumps(variant))

        trace_dir = tmp_path / "trace"
        monkeypatch.setenv("PIO_PROFILE_DIR", str(trace_dir))
        assert main(["train", "--engine-variant", str(variant_path)]) == 0
        assert "Training completed" in capsys.readouterr().out
        assert list(trace_dir.rglob("*")), "no profiler trace written"
        # DASE stage histograms saw the pass
        for stage in ("read", "prepare", "train"):
            assert metrics.TRAIN_STAGE_LATENCY.child(
                stage=stage).summary()["count"] >= 1

    def test_metrics_flag_off(self):
        from predictionio_tpu.tools import run_commands
        from predictionio_tpu.tools.cli import build_parser

        prior = metrics.REGISTRY.enabled
        try:
            args = build_parser().parse_args(
                ["eventserver", "--metrics", "off"])
            run_commands._apply_metrics_flag(args)
            assert metrics.REGISTRY.enabled is False
            args = build_parser().parse_args(
                ["deploy", "--metrics", "on"])
            run_commands._apply_metrics_flag(args)
            assert metrics.REGISTRY.enabled is True
        finally:
            metrics.set_enabled(prior)


class TestMicroBatcherStats:
    def test_stats_snapshot_consistent(self):
        import threading

        import numpy as np

        from predictionio_tpu.ops.serving import DeviceTopK

        rng = np.random.default_rng(0)
        srv = DeviceTopK(rng.normal(size=(32, 8)).astype(np.float32),
                         rng.normal(size=(16, 8)).astype(np.float32),
                         microbatch=True)
        try:
            q0 = metrics.MICROBATCH_QUERIES.value(batcher="pio-microbatch")

            def client(tx):
                for i in range(10):
                    srv.user_topk((tx * 10 + i) % 32, 4)

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = srv.stats()
            assert stats["users"]["batchedQueries"] == 40
            assert 1 <= stats["users"]["dispatches"] <= 40
            assert stats["users"]["queueDepth"] == 0
            assert metrics.MICROBATCH_QUERIES.value(
                batcher="pio-microbatch") == q0 + 40
        finally:
            srv.close()
