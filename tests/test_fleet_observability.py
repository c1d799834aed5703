"""Fleet observability plane suite (PR 19).

Differentials are the backbone: the federated ``/metrics`` must be
*provably* the sum of its member scrapes — counters equal the sum,
merged histogram cumulative buckets equal merging the member snapshots
by hand, and a version-skewed member (mismatched histogram bounds)
surfaces as a scrape problem instead of corrupting the fleet series.
A dead member degrades the scrape (``member_down``) and recovers; an
in-process member (shares this process's registry) is excluded from
the merge so nothing double-counts. On top: the SLO burn-rate engine
(fires on sustained budget burn over both windows, clears on
recovery, flips balancer readiness) and live cross-process trace
assembly through the balancer's ``GET /traces/<id>``.
"""

import datetime as dt
import http.client
import json
import os
import threading
import time
import urllib.request

import pytest

from predictionio_tpu.data import storage as storage_mod
from predictionio_tpu.obs import assemble
from predictionio_tpu.obs import federation as fed
from predictionio_tpu.obs import slo as slo_mod
from predictionio_tpu.utils import faults, metrics, resilience
from predictionio_tpu.utils.http_instrumentation import (
    SeveringThreadingHTTPServer,
)
from predictionio_tpu.utils.tracing import LatencyHistogram

from test_tracing import traces  # noqa: F401  (fixture reuse)

pytestmark = pytest.mark.fleet

UTC = dt.timezone.utc


@pytest.fixture(autouse=True)
def _clean_resilience():
    resilience.reset_breakers()
    faults.clear()
    yield
    resilience.reset_breakers()
    faults.clear()


# ---------------------------------------------------------------------------
# Fake fleet members: real HTTP servers over their OWN registries
# ---------------------------------------------------------------------------

from http.server import BaseHTTPRequestHandler  # noqa: E402


class FakeMember:
    """A member-shaped HTTP server: /metrics from its own registry,
    /healthz with a configurable pid, /stats.json, /traces endpoints —
    millisecond-fast stand-in for a real event-server process."""

    def __init__(self, pid=None, port=0, ready=True):
        self.registry = metrics.MetricsRegistry(enabled=True)
        self.pid = os.getpid() + 70000 if pid is None else pid
        self.ready = ready
        self.trace_records = {}
        self.slow_log = []
        member = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, status, body, ctype="application/json"):
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    self._send(
                        200,
                        member.registry.render_prometheus().encode(),
                        "text/plain; version=0.0.4; charset=utf-8")
                elif path == "/healthz":
                    self._send(
                        200 if member.ready else 503,
                        json.dumps({"alive": True,
                                    "ready": member.ready,
                                    "checks": {"storage": member.ready},
                                    "server": "eventserver",
                                    "pid": member.pid}).encode())
                elif path == "/stats.json":
                    self._send(200, json.dumps(
                        {"status": "alive"}).encode())
                elif path == "/traces.json":
                    self._send(200, json.dumps(
                        {"traces": [], "slowLog": member.slow_log})
                        .encode())
                elif path.startswith("/traces/"):
                    rec = member.trace_records.get(
                        path[len("/traces/"):])
                    if rec is None:
                        self._send(404, b"{}")
                    else:
                        self._send(200, json.dumps(rec).encode())
                else:
                    self._send(404, b"{}")

        self.httpd = SeveringThreadingHTTPServer(("127.0.0.1", port),
                                                 Handler)
        self.httpd.daemon_threads = True
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)
        self.thread.start()

    @property
    def url(self):
        host, port = self.httpd.server_address[:2]
        return f"http://{host}:{port}"

    @property
    def port(self):
        return self.httpd.server_address[1]

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(timeout=5)


def _count(reg, name, n, **labels):
    c = reg.get(name) or reg.counter(
        name, "test counter", tuple(sorted(labels)))
    c.inc(n, **labels)


# ---------------------------------------------------------------------------
# parse_prometheus: inverse of the renderer
# ---------------------------------------------------------------------------

class TestParsePrometheus:
    def test_round_trips_the_renderer(self):
        reg = metrics.MetricsRegistry(enabled=True)
        c = reg.counter("pio_obs_events_total", "events",
                        ("kind", "status"))
        c.inc(7, kind="rate", status="201")
        c.inc(2, kind='we"ird\\one\nx', status="400")
        g = reg.gauge("pio_obs_depth", "depth", ("lane",))
        g.set(3.5, lane="a")
        h = reg.histogram("pio_obs_seconds", "lat", ("route",))
        for v in (0.003, 0.02, 0.4, 9.0):
            h.observe(v, route="/x")
        snap = reg.snapshot()
        parsed = metrics.parse_prometheus(reg.render_prometheus())
        assert sorted(parsed) == sorted(snap)
        for name in snap:
            assert parsed[name]["type"] == snap[name]["type"]
        # counters/gauges byte-for-byte
        def series_map(fam):
            return {tuple(sorted(e["labels"].items())): e["value"]
                    for e in fam["series"]}
        assert series_map(parsed["pio_obs_events_total"]) == \
            series_map(snap["pio_obs_events_total"])
        assert series_map(parsed["pio_obs_depth"]) == \
            series_map(snap["pio_obs_depth"])
        # histogram buckets exactly (max/last are not carried by text)
        pe = parsed["pio_obs_seconds"]["series"][0]
        se = snap["pio_obs_seconds"]["series"][0]
        assert pe["buckets"] == se["buckets"]
        assert pe["count"] == se["count"]
        assert pe["sum"] == pytest.approx(se["sum"])

    def test_malformed_sample_raises(self):
        with pytest.raises(metrics.MetricError):
            metrics.parse_prometheus('pio_x{le="0.1\n')
        with pytest.raises(ValueError):
            metrics.parse_prometheus("pio_x notanumber")


# ---------------------------------------------------------------------------
# Satellite 2: histogram merge with custom/mismatched bounds
# ---------------------------------------------------------------------------

class TestHistogramBoundsSkew:
    def test_merge_refuses_mismatched_bounds(self):
        a = LatencyHistogram(bounds=(0.1, 0.5))
        b = LatencyHistogram(bounds=(0.1, 0.5, 2.0))
        a.record(0.2)
        b.record(0.2)
        with pytest.raises(ValueError, match="different bounds"):
            a.merge(b)

    def test_from_state_round_trip_merges_like_live(self):
        bounds = (0.05, 0.25, 1.0)
        a = LatencyHistogram(bounds=bounds)
        b = LatencyHistogram(bounds=bounds)
        for v in (0.01, 0.1, 0.9, 3.0):
            a.record(v)
        for v in (0.2, 0.2, 5.0):
            b.record(v)
        rebuilt = LatencyHistogram.from_state(
            bounds, b.snapshot()[0], total=b.snapshot()[1],
            sum_sec=b.snapshot()[2], max_sec=b.snapshot()[3],
            last_sec=b.snapshot()[4])
        direct = LatencyHistogram(bounds=bounds)
        direct.merge(a)
        direct.merge(b)
        via_state = LatencyHistogram(bounds=bounds)
        via_state.merge(a)
        via_state.merge(rebuilt)
        assert direct.snapshot() == via_state.snapshot()

    def test_histogram_from_snapshot_rejects_garbage(self):
        with pytest.raises(metrics.MetricError):
            metrics.histogram_from_snapshot({"buckets": []})
        with pytest.raises(metrics.MetricError):  # missing +Inf
            metrics.histogram_from_snapshot(
                {"buckets": [{"le": "0.1", "cumulative": 2}],
                 "count": 2, "sum": 0.1})
        with pytest.raises(metrics.MetricError):  # non-monotonic
            metrics.histogram_from_snapshot(
                {"buckets": [{"le": "0.1", "cumulative": 5},
                             {"le": "+Inf", "cumulative": 2}],
                 "count": 2, "sum": 0.1})

    def test_federation_reports_bounds_skew_instead_of_crashing(self):
        reg_a = metrics.MetricsRegistry(enabled=True)
        reg_b = metrics.MetricsRegistry(enabled=True)
        reg_a.histogram("pio_skewed_seconds", "lat", ("r",),
                        buckets=(0.1, 1.0)).observe(0.2, r="/x")
        reg_b.histogram("pio_skewed_seconds", "lat", ("r",),
                        buckets=(0.5, 2.0)).observe(0.2, r="/x")
        merged, problems = fed.merge_member_families(
            [("a", reg_a.snapshot()), ("b", reg_b.snapshot())])
        assert any(p["family"] == "pio_skewed_seconds"
                   and "bounds" in p["problem"] for p in problems)
        # the first member's series survives; the skewed one is out
        fam = merged["pio_skewed_seconds"]
        assert len(fam["series"]) == 1
        assert fam["series"][0]["count"] == 1


# ---------------------------------------------------------------------------
# Merge differential: fleet view == hand-merged member snapshots
# ---------------------------------------------------------------------------

class TestMergeDifferential:
    def _registries(self):
        regs = []
        for i, n in enumerate((3, 5, 11)):
            reg = metrics.MetricsRegistry(enabled=True)
            _count(reg, "pio_obs_events_total", n, kind="rate")
            _count(reg, "pio_obs_events_total", i + 1, kind="set")
            reg.gauge("pio_obs_queue", "q", ()).set(float(i))
            h = reg.histogram("pio_obs_lat_seconds", "lat", ("route",))
            for k in range(n):
                h.observe(0.01 * (k + 1) * (i + 1), route="/q")
            regs.append(reg)
        return regs

    def test_counters_sum_exactly(self):
        regs = self._registries()
        merged, problems = fed.merge_member_families(
            [(f"m{i}", r.snapshot()) for i, r in enumerate(regs)])
        assert problems == []
        by_kind = {e["labels"]["kind"]: e["value"]
                   for e in merged["pio_obs_events_total"]["series"]}
        assert by_kind == {"rate": 3 + 5 + 11, "set": 1 + 2 + 3}

    def test_gauges_stay_per_member(self):
        regs = self._registries()
        merged, _ = fed.merge_member_families(
            [(f"m{i}", r.snapshot()) for i, r in enumerate(regs)])
        series = merged["pio_obs_queue"]["series"]
        assert {(e["labels"]["member"], e["value"]) for e in series} == \
            {("m0", 0.0), ("m1", 1.0), ("m2", 2.0)}

    def test_histogram_buckets_equal_hand_merge(self):
        regs = self._registries()
        snaps = [r.snapshot() for r in regs]
        merged, _ = fed.merge_member_families(
            [(f"m{i}", s) for i, s in enumerate(snaps)])
        got = merged["pio_obs_lat_seconds"]["series"][0]
        # hand merge: de-cumulate each member, sum, re-cumulate
        member_entries = [s["pio_obs_lat_seconds"]["series"][0]
                          for s in snaps]
        les = [b["le"] for b in member_entries[0]["buckets"]]
        per_bucket = [0] * len(les)
        for e in member_entries:
            prev = 0
            for j, b in enumerate(e["buckets"]):
                per_bucket[j] += b["cumulative"] - prev
                prev = b["cumulative"]
        acc, expect = 0, []
        for le, c in zip(les, per_bucket):
            acc += c
            expect.append({"le": le, "cumulative": acc})
        assert got["buckets"] == expect
        assert got["count"] == sum(e["count"] for e in member_entries)
        assert got["sum"] == pytest.approx(
            sum(e["sum"] for e in member_entries))
        assert got["max"] == max(e["max"] for e in member_entries)


# ---------------------------------------------------------------------------
# Satellite 3: scrape differential over real HTTP members
# ---------------------------------------------------------------------------

class TestFederationScrape:
    @pytest.fixture
    def members(self):
        ms = [FakeMember(), FakeMember()]
        yield ms
        for m in ms:
            try:
                m.stop()
            except Exception:
                pass

    def _federation(self, members):
        targets = [(f"shard{i}", m.url) for i, m in enumerate(members)]
        return fed.FleetFederation(targets=lambda: list(targets))

    def test_fleet_counters_equal_sum_of_member_scrapes(self, members):
        for i, m in enumerate(members):
            _count(m.registry, "pio_obsfake_total", 10 + i, kind="x")
        f = self._federation(members)
        sc = f.observe()
        try:
            rows = {r["member"]: r for r in sc.members}
            assert rows["balancer"]["local"] is True
            assert rows["shard0"]["ok"] and rows["shard1"]["ok"]
            assert rows["shard0"]["pid"] == members[0].pid
            val = sc.merged["pio_obsfake_total"]["series"][0]["value"]
            assert val == 10 + 11
            # the exposition re-parses to the same sum, with member
            # drill-down series preserved
            parsed = metrics.parse_prometheus(sc.prometheus())
            fam = parsed["pio_obsfake_total"]["series"]
            merged_series = [e for e in fam
                             if "member" not in e["labels"]]
            drill = {e["labels"]["member"]: e["value"] for e in fam
                     if "member" in e["labels"]}
            assert merged_series[0]["value"] == 21
            assert drill == {"shard0": 10.0, "shard1": 11.0}
        finally:
            f.close()

    def test_dead_member_degrades_and_recovers(self, members):
        _count(members[0].registry, "pio_obsfake_total", 4, kind="x")
        _count(members[1].registry, "pio_obsfake_total", 6, kind="x")
        f = self._federation(members)
        try:
            sc = f.observe()
            assert all(r["ok"] for r in sc.members)
            port = members[1].port
            members[1].stop()
            sc = f.observe()
            rows = {r["member"]: r for r in sc.members}
            assert rows["shard1"]["ok"] is False
            assert rows["shard1"]["reason"] == "member_down"
            assert "error" in rows["shard1"]
            # the scrape DEGRADED: shard0's series still merged
            assert sc.merged["pio_obsfake_total"]["series"][0][
                "value"] == 4
            # scrape failures never touch the serving-path breaker
            assert not resilience.breaker_for(
                members[1].url).is_blocking
            # recovery: same port, fresh member
            members[1] = FakeMember(port=port)
            _count(members[1].registry, "pio_obsfake_total", 6,
                   kind="x")
            resilience.reset_breakers()
            sc = f.observe()
            rows = {r["member"]: r for r in sc.members}
            assert rows["shard1"]["ok"] is True
            assert sc.merged["pio_obsfake_total"]["series"][0][
                "value"] == 10
        finally:
            f.close()

    def test_in_process_member_not_double_counted(self, members):
        # a member claiming OUR pid shares our registry: flagged and
        # excluded from the merge
        inproc = FakeMember(pid=os.getpid())
        _count(inproc.registry, "pio_obsfake_inproc_total", 9, kind="x")
        f = fed.FleetFederation(
            targets=lambda: [("shard0", inproc.url)])
        try:
            sc = f.observe()
            row = {r["member"]: r for r in sc.members}["shard0"]
            assert row["ok"] is True
            assert row["inProcess"] is True
            assert "pio_obsfake_inproc_total" not in sc.merged
        finally:
            f.close()
            inproc.stop()

    def test_not_ready_member_still_scrapes(self, members):
        sick = FakeMember(ready=False)
        _count(sick.registry, "pio_obsfake_sick_total", 2, kind="x")
        f = fed.FleetFederation(targets=lambda: [("shard0", sick.url)])
        try:
            sc = f.observe()
            row = {r["member"]: r for r in sc.members}["shard0"]
            assert row["ok"] is True          # alive and answering
            assert row["ready"] is False      # ...but not ready
            assert sc.merged["pio_obsfake_sick_total"]["series"][0][
                "value"] == 2
        finally:
            f.close()
            sick.stop()


# ---------------------------------------------------------------------------
# Trace assembly (shared fold + live dedup)
# ---------------------------------------------------------------------------

class TestAssemble:
    def _frag(self, tid, spans, duration=1.0, error=False, pid=1):
        return {"traceId": tid, "root": spans[0]["name"],
                "durationSec": duration, "slow": False, "error": error,
                "process": {"pid": pid},
                "spans": [dict(s, pid=s.get("pid", pid)) for s in spans]}

    def test_topmost_fragment_names_the_trace(self):
        tid = "ab" * 16
        remote = self._frag(tid, [
            {"spanId": "r1", "parentId": "l2",
             "name": "event GET /x"}], pid=2)
        local = self._frag(tid, [
            {"spanId": "l1", "parentId": None, "name": "pio.query"},
            {"spanId": "l2", "parentId": "l1", "name": "wire"}], pid=1)
        # remote arrives FIRST: the topmost (local) fragment must still
        # win the root naming
        rec = assemble.assemble([remote, local])
        assert rec["spans"][0]["name"] == "pio.query"
        assert {s["spanId"] for s in rec["spans"]} == {"l1", "l2", "r1"}
        assert rec["processes"] == [1, 2]

    def test_duplicate_spans_deduped(self):
        tid = "cd" * 16
        a = self._frag(tid, [
            {"spanId": "s1", "parentId": None, "name": "root"},
            {"spanId": "s2", "parentId": "s1", "name": "child"}])
        dup = self._frag(tid, [
            {"spanId": "s1", "parentId": None, "name": "root"},
            {"spanId": "s2", "parentId": "s1", "name": "child"}])
        rec = assemble.assemble([a, dup])
        assert len(rec["spans"]) == 2

    def test_error_and_duration_fold(self):
        tid = "ef" * 16
        a = self._frag(tid, [{"spanId": "x", "parentId": None,
                              "name": "r"}], duration=0.5)
        b = self._frag(tid, [{"spanId": "y", "parentId": "x",
                              "name": "c"}], duration=2.0, error=True,
                       pid=2)
        rec = assemble.assemble([a, b])
        assert rec["durationSec"] == 2.0
        assert rec["error"] is True

    def test_assemble_of_nothing_is_none(self):
        assert assemble.assemble([None, {}, {"spans": []}]) is None


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def _slo_snapshot(total=0, errors=0, slow=0, degraded=0):
    """A merged-snapshot shape with balancer /queries.json traffic:
    ``slow`` of ``total`` requests land above 0.5s."""
    ok = total - errors
    counters = {
        "type": "counter", "help": "", "series": [
            {"labels": {"server": "balancer", "route": "/queries.json",
                        "method": "POST", "status": "200"},
             "value": float(ok)},
            {"labels": {"server": "balancer", "route": "/queries.json",
                        "method": "POST", "status": "503"},
             "value": float(errors)},
        ]}
    fast = total - slow
    hist = {
        "type": "histogram", "help": "", "series": [
            {"labels": {"server": "balancer", "route": "/queries.json"},
             "count": total, "sum": 0.01 * fast + 1.0 * slow,
             "max": 1.0 if slow else 0.01, "last": 0.01,
             "buckets": [{"le": "0.1", "cumulative": fast},
                         {"le": "0.5", "cumulative": fast},
                         {"le": "+Inf", "cumulative": total}]}]}
    out = {"pio_http_requests_total": counters,
           "pio_http_request_seconds": hist}
    if degraded:
        out["pio_degraded_queries_total"] = {
            "type": "counter", "help": "", "series": [
                {"labels": {"reason": "storage_down"},
                 "value": float(degraded)}]}
    return out


class TestSLOEngine:
    def _engine(self, fast=60.0, slow=300.0, threshold=10.0):
        cfg = slo_mod.SLOConfig(fast_window_sec=fast,
                                slow_window_sec=slow,
                                burn_threshold=threshold)
        return slo_mod.SLOEngine(cfg)

    def test_quiet_fleet_never_fires(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=0), now=0.0)
        blk = eng.evaluate(_slo_snapshot(total=500), now=30.0)
        assert blk["firing"] == []
        for obj in blk["objectives"].values():
            assert obj["burn"] == {"fast": 0.0, "slow": 0.0}
            assert obj["budgetRemaining"] == 1.0

    def test_error_burn_fires_and_clears(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=100), now=0.0)
        blk = eng.evaluate(_slo_snapshot(total=200, errors=50), now=30.0)
        # 50/100 new requests failed: burn = 0.5/0.01 = 50 >= 10 on
        # both (history-shrunk) windows
        assert "error_rate" in blk["firing"]
        obj = blk["objectives"]["error_rate"]
        assert obj["burn"]["fast"] == pytest.approx(50.0)
        assert obj["firing"] is True and "since" in obj
        assert obj["budgetRemaining"] == -1.0  # clamped
        # recovery: errors stop; once the windows roll past the bad
        # era the burn is 0 again
        eng.evaluate(_slo_snapshot(total=300, errors=50), now=60.0)
        blk = eng.evaluate(_slo_snapshot(total=900, errors=50),
                           now=400.0)
        assert blk["firing"] == []
        assert blk["objectives"]["error_rate"]["burn"]["slow"] == 0.0

    def test_latency_objective_is_bucket_exact(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=0), now=0.0)
        blk = eng.evaluate(_slo_snapshot(total=100, slow=20), now=30.0)
        obj = blk["objectives"]["query_latency_p99"]
        # 20% above 0.5s against a 1% budget = burn 20
        assert obj["burn"]["fast"] == pytest.approx(20.0)
        assert "query_latency_p99" in blk["firing"]

    def test_degraded_objective(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=0), now=0.0)
        blk = eng.evaluate(_slo_snapshot(total=100, degraded=80),
                           now=30.0)
        # 80% degraded against a 5% budget = burn 16
        assert blk["objectives"]["degraded_rate"]["burn"]["fast"] == \
            pytest.approx(16.0)
        assert "degraded_rate" in blk["firing"]

    def test_gauges_exported(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=100), now=0.0)
        eng.evaluate(_slo_snapshot(total=200, errors=50), now=30.0)
        assert slo_mod.SLO_BURN_RATE.value(
            objective="error_rate", window="fast") == pytest.approx(50.0)
        assert slo_mod.SLO_BUDGET_REMAINING.value(
            objective="error_rate") == -1.0

    def test_single_burst_does_not_fire_without_bad_delta(self):
        eng = self._engine()
        eng.evaluate(_slo_snapshot(total=100, errors=5), now=0.0)
        # no NEW errors after the baseline: deltas carry no bad
        blk = eng.evaluate(_slo_snapshot(total=200, errors=5), now=30.0)
        assert blk["firing"] == []


class TestSLOConfig:
    def test_defaults(self):
        cfg = slo_mod.load_slo_config(env={})
        assert cfg.fast_window_sec == 300.0
        assert cfg.slow_window_sec == 3600.0
        assert cfg.burn_threshold == 14.4
        assert set(cfg.objectives) == {"query_latency_p99",
                                       "error_rate", "degraded_rate"}
        assert cfg.objectives["query_latency_p99"].threshold_sec == 0.5

    def test_inline_json_and_env_overrides(self):
        env = {"PIO_SLO_CONFIG":
               '{"fastWindowSec": 30, "burnThreshold": 5,'
               ' "objectives": {"error_rate": {"budget": 0.02},'
               '  "degraded_rate": {"disabled": true}}}',
               "PIO_SLO_QUERY_LATENCY_P99_TARGET_SEC": "0.25"}
        cfg = slo_mod.load_slo_config(env=env)
        assert cfg.fast_window_sec == 30.0
        assert cfg.burn_threshold == 5.0
        assert cfg.objectives["error_rate"].budget == 0.02
        assert cfg.objectives["degraded_rate"].disabled is True
        assert cfg.objectives["query_latency_p99"].threshold_sec == 0.25

    def test_file_path_and_explicit_precedence(self, tmp_path):
        p = tmp_path / "slo.json"
        p.write_text('{"slowWindowSec": 600}')
        cfg = slo_mod.load_slo_config(
            explicit=str(p),
            env={"PIO_SLO_CONFIG": '{"slowWindowSec": 1200}'})
        assert cfg.slow_window_sec == 600.0  # --slo-config wins

    def test_invalid_windows_rejected(self):
        with pytest.raises(ValueError):
            slo_mod.load_slo_config(
                env={"PIO_SLO_FAST_WINDOW_SEC": "600",
                     "PIO_SLO_SLOW_WINDOW_SEC": "60"})
        with pytest.raises(ValueError):
            slo_mod.load_slo_config(
                env={"PIO_SLO_CONFIG":
                     '{"objectives": {"mystery": {"budget": 0.1}}}'})


# ---------------------------------------------------------------------------
# Balancer integration: federated endpoints on a live fleet
# ---------------------------------------------------------------------------

def _get(addr, path, headers=None):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    ct = resp.getheader("Content-Type") or ""
    conn.close()
    return resp.status, data, ct


class TestBalancerObservability:
    @pytest.fixture
    def fleet(self, mem_storage, monkeypatch):
        from test_query_server import seed_ratings, train_once
        from predictionio_tpu.fleet.balancer import QueryFleet
        from predictionio_tpu.workflow import ServerConfig

        monkeypatch.setenv("PIO_SLO_POLL_SEC", "0")
        seed_ratings()
        train_once()
        qf = QueryFleet(ServerConfig(ip="127.0.0.1", port=0),
                        replicas=3).start(undeploy_stale=False)
        yield qf
        qf.stop()

    def _post_query(self, addr, body, headers=None):
        host, port = addr
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/queries.json",
                     body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        data = resp.read()
        hdrs = dict(resp.getheaders())
        conn.close()
        return resp.status, json.loads(data), hdrs

    def test_balancer_route_metrics_and_request_id_echo(self, fleet):
        """Satellite 1: the balancer is instrumented like the other
        five servers — server="balancer" route counters/latency,
        request-id echo, HTTP/1.1 keep-alive."""
        before = metrics.HTTP_REQUESTS.value(
            server="balancer", route="/queries.json", method="POST",
            status="200")
        host, port = fleet.address
        conn = http.client.HTTPConnection(host, port, timeout=30)
        sock_id = None
        for i in range(3):
            conn.request("POST", "/queries.json",
                         body=json.dumps({"user": "u1", "num": 2}),
                         headers={"Content-Type": "application/json",
                                  "X-Request-ID": f"obs-rid-{i}"})
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            assert resp.getheader("X-Request-ID") == f"obs-rid-{i}"
            if sock_id is None:
                sock_id = id(conn.sock)
            else:  # same socket: keep-alive held across requests
                assert id(conn.sock) == sock_id
        conn.close()
        # a request is counted after its response has gone out, so the
        # third count can land a moment after the client has its answer
        deadline = time.time() + 5.0
        while True:
            after = metrics.HTTP_REQUESTS.value(
                server="balancer", route="/queries.json", method="POST",
                status="200")
            if after - before >= 3 or time.time() > deadline:
                break
            time.sleep(0.01)
        assert after - before == 3
        lat = metrics.REGISTRY.snapshot()["pio_http_request_seconds"]
        assert any(e["labels"] == {"server": "balancer",
                                   "route": "/queries.json"}
                   for e in lat["series"])

    def test_federated_metrics_exposition(self, fleet):
        self._post_query(fleet.address, {"user": "u2", "num": 2})
        status, body, ctype = _get(fleet.address, "/metrics")
        assert status == 200 and "version=0.0.4" in ctype
        parsed = metrics.parse_prometheus(body.decode())
        fam = parsed["pio_http_requests_total"]["series"]
        merged = [e for e in fam if "member" not in e["labels"]]
        drill = [e for e in fam if e["labels"].get("member")
                 == "balancer"]
        assert merged and drill
        # single-member fleet (memory storage, no shards): the merged
        # counters equal the balancer drill-down exactly
        def key(e):
            return tuple(sorted((k, v) for k, v in e["labels"].items()
                                if k != "member"))
        merged_map = {key(e): e["value"] for e in merged}
        drill_map = {key(e): e["value"] for e in drill}
        assert merged_map == drill_map
        assert "pio_slo_burn_rate" in parsed

    def test_stats_json_fleet_block_and_healthz(self, fleet):
        status, body, _ = _get(fleet.address, "/stats.json")
        assert status == 200
        stats = json.loads(body)
        topo = stats["fleet"]
        # PR-18 compat keys intact
        assert topo["type"] == "queryFleet"
        assert topo["readyReplicas"] == 3
        assert len(topo["replicas"]) == 3
        # the new federation block
        members = {m["member"]: m for m in topo["members"]}
        assert members["balancer"]["local"] is True
        assert members["balancer"]["pid"] == os.getpid()
        assert topo["scrape"]["problems"] == []
        assert topo["scrape"]["durationSec"] >= 0
        assert "at" in topo["scrape"]
        # alerts block + readiness detail
        assert stats["alerts"]["firing"] == []
        assert "degraded_rate" in stats["alerts"]["objectives"]
        status, body, _ = _get(fleet.address, "/healthz")
        health = json.loads(body)
        assert status == 200 and health["ready"] is True
        assert health["checks"]["slo_alerts"] is True
        assert health["pid"] == os.getpid()

    def test_live_trace_assembly_through_balancer(self, fleet,
                                                  traces):  # noqa: F811
        tid = "ab" * 16
        client_trace = f"00-{tid}-{'6d' * 8}-01"
        status, payload, hdrs = self._post_query(
            fleet.address, {"user": "u1", "num": 2},
            headers={"traceparent": client_trace})
        assert status == 200 and payload["itemScores"]
        # poll: the live read can race the balancer root-span flush
        rec, names = None, set()
        for _ in range(40):
            status, body, _ = _get(fleet.address, f"/traces/{tid}")
            if status == 200:
                rec = json.loads(body)
                names = {s["name"] for s in rec["spans"]}
                if "balancer POST /queries.json" in names:
                    break
            time.sleep(0.05)
        assert rec is not None and rec["traceId"] == tid
        # balancer AND replica legs of the same trace, one record
        assert "balancer POST /queries.json" in names
        assert "query POST /queries.json" in names
        assert "serve.predict" in names
        by_id = {s["spanId"]: s for s in rec["spans"]}
        replica_http = next(s for s in rec["spans"]
                            if s["name"] == "query POST /queries.json")
        assert replica_http["parentId"] in by_id
        # all three formats render the assembled record
        status, body, _ = _get(fleet.address,
                               f"/traces/{tid}?format=perfetto")
        assert status == 200
        assert json.loads(body)["traceEvents"]
        status, body, ctype = _get(fleet.address,
                                   f"/traces/{tid}?format=html")
        assert status == 200 and ctype.startswith("text/html")
        assert tid.encode() in body

    def test_trace_404_and_traces_json(self, fleet, traces):  # noqa: F811
        status, body, _ = _get(fleet.address, "/traces/" + "00" * 16)
        assert status == 404
        status, body, _ = _get(fleet.address, "/traces.json")
        assert status == 200
        doc = json.loads(body)
        assert set(doc) >= {"enabled", "traces", "slowLog"}


# ---------------------------------------------------------------------------
# Fleet storage integration: event shards as federation members
# ---------------------------------------------------------------------------

class TestFleetStorageFederation:
    @pytest.fixture
    def shard_fleet(self, tmp_path, monkeypatch):
        from test_fleet import KEY, ShardCluster

        monkeypatch.setenv("PIO_SLO_POLL_SEC", "0")
        cluster = ShardCluster("memory", tmp_path, n=2)
        cfg = storage_mod.StorageConfig(
            sources={"FLEET": {"type": "fleet",
                               "urls": ",".join(cluster.urls),
                               "service_key": KEY},
                     "META": {"type": "memory"}},
            repositories={"EVENTDATA": "FLEET", "METADATA": "META",
                          "MODELDATA": "META"})
        storage_mod.reset(cfg)
        yield cluster
        storage_mod.reset()
        cluster.close()

    @pytest.fixture
    def fleet(self, shard_fleet):
        from test_query_server import seed_ratings, train_once
        from predictionio_tpu.fleet.balancer import QueryFleet
        from predictionio_tpu.workflow import ServerConfig

        seed_ratings()
        train_once()
        qf = QueryFleet(ServerConfig(ip="127.0.0.1", port=0),
                        replicas=2).start(undeploy_stale=False)
        yield qf
        qf.stop()

    def test_shards_are_members_and_dead_shard_degrades(
            self, shard_fleet, fleet):
        status, body, _ = _get(fleet.address, "/stats.json")
        assert status == 200
        stats = json.loads(body)
        members = {m["member"]: m for m in stats["fleet"]["members"]}
        assert set(members) == {"balancer", "shard0", "shard1"}
        # in-process shards share our registry: flagged, not merged
        for name in ("shard0", "shard1"):
            assert members[name]["ok"] is True
            assert members[name]["inProcess"] is True
            assert members[name]["url"] in shard_fleet.urls
        # kill one shard: the scrape degrades, never fails
        shard_fleet.kill_shard(1)
        status, body, _ = _get(fleet.address, "/stats.json")
        assert status == 200
        stats = json.loads(body)
        members = {m["member"]: m for m in stats["fleet"]["members"]}
        assert members["shard1"]["ok"] is False
        assert members["shard1"]["reason"] == "member_down"
        assert members["shard0"]["ok"] is True
        # recovery
        shard_fleet.restart_shard(1)
        resilience.reset_breakers()
        status, body, _ = _get(fleet.address, "/stats.json")
        members = {m["member"]: m
                   for m in json.loads(body)["fleet"]["members"]}
        assert members["shard1"]["ok"] is True


# ---------------------------------------------------------------------------
# SLO alerts fire under injected faults and clear on recovery
# ---------------------------------------------------------------------------

class TestSLOAlertsLive:
    @pytest.fixture
    def degrading_fleet(self, mem_storage, monkeypatch):
        import numpy as np

        from predictionio_tpu.controller import ComputeContext
        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.fleet.balancer import QueryFleet
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates import recommendation as rec_tpl
        from predictionio_tpu.workflow import ServerConfig, run_train
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig, new_engine_instance,
        )
        from test_query_server import seed_ratings

        _ = np  # seed_ratings uses it internally

        class DegradingALS(rec_tpl.ALSAlgorithm):
            """Predict-time storage read: under injected storage
            faults every query marks the serving degraded scope."""

            def predict(self, model, query):
                try:
                    next(iter(storage_mod.get_levents().find(
                        1, limit=1)), None)
                except Exception:
                    resilience.mark_degraded("storage_down")
                return super().predict(model, query)

        # tiny windows + a low threshold so fire/clear happens in
        # test time, not SRE time
        monkeypatch.setenv(
            "PIO_SLO_CONFIG",
            '{"fastWindowSec": 0.5, "slowWindowSec": 1.0,'
            ' "burnThreshold": 2.0}')
        monkeypatch.setenv("PIO_SLO_POLL_SEC", "0")
        seed_ratings()
        engine = rec_tpl.engine_factory().copy(
            algorithm_class_map={"als": DegradingALS})
        params = EngineParams(
            data_source_params=("", rec_tpl.DataSourceParams(
                app_name="recapp")),
            algorithm_params_list=[
                ("als", ALSParams(rank=4, num_iterations=2, seed=0))])
        instance = new_engine_instance(
            WorkflowConfig(engine_factory="test:slo"), params)
        iid = run_train(engine, params, instance, ctx=ComputeContext())
        assert iid is not None
        qf = QueryFleet(
            ServerConfig(ip="127.0.0.1", port=0,
                         engine_instance_id=iid),
            replicas=2, engine=engine).start(undeploy_stale=False)
        yield qf
        qf.stop()

    def _post(self, addr, body):
        host, port = addr
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/queries.json",
                     body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = json.loads(resp.read())
        conn.close()
        return resp.status, data

    def test_alerts_fire_under_faults_and_clear_on_recovery(
            self, degrading_fleet):
        addr = degrading_fleet.address
        # baseline: healthy traffic, one observation
        for i in range(3):
            status, payload = self._post(addr, {"user": f"u{i}",
                                                "num": 2})
            assert status == 200 and not payload.get("degraded")
        status, body, _ = _get(addr, "/stats.json")
        assert json.loads(body)["alerts"]["firing"] == []

        # inject: every storage read errors -> every query degrades
        faults.install("backend=memory,op=find*,kind=error,rate=1")
        for i in range(6):
            status, payload = self._post(addr, {"user": f"u{i}",
                                                "num": 2})
            assert status == 200
            assert payload.get("degraded") is True
            assert "storage_down" in payload.get("degradedReasons", [])
        status, body, _ = _get(addr, "/stats.json")
        stats = json.loads(body)
        assert "degraded_rate" in stats["alerts"]["firing"]
        obj = stats["alerts"]["objectives"]["degraded_rate"]
        assert obj["firing"] is True
        assert obj["burn"]["fast"] >= 2.0
        # the alert shows up in the federated exposition...
        status, body, _ = _get(addr, "/metrics")
        parsed = metrics.parse_prometheus(body.decode())
        # SLO gauges are member-scoped (gauge merge semantics): the
        # balancer evaluates, so its member label carries the burn
        burn = {(e["labels"]["objective"], e["labels"]["window"]):
                e["value"]
                for e in parsed["pio_slo_burn_rate"]["series"]
                if e["labels"].get("member") == "balancer"}
        assert burn[("degraded_rate", "fast")] >= 2.0
        # ...and flips readiness (liveness stays: the server answers)
        status, body, _ = _get(addr, "/healthz")
        health = json.loads(body)
        assert status == 503
        assert health["alive"] is True
        assert health["checks"]["slo_alerts"] is False

        # recovery: clear the faults (and the breaker the fault era
        # opened), let the windows roll past the bad era, serve clean
        # traffic
        faults.clear()
        resilience.reset_breakers()
        _get(addr, "/stats.json")  # post-recovery cumulative sample
        time.sleep(1.2)            # > slowWindowSec
        for i in range(4):
            status, payload = self._post(addr, {"user": f"u{i}",
                                                "num": 2})
            assert status == 200 and not payload.get("degraded")
        status, body, _ = _get(addr, "/stats.json")
        stats = json.loads(body)
        assert stats["alerts"]["firing"] == []
        assert stats["alerts"]["objectives"]["degraded_rate"][
            "firing"] is False
        status, body, _ = _get(addr, "/healthz")
        assert status == 200
        assert json.loads(body)["checks"]["slo_alerts"] is True


# ---------------------------------------------------------------------------
# Three processes, one trace, assembled at the balancer (acceptance)
# ---------------------------------------------------------------------------

from test_tracing import remote_event_server  # noqa: F401,E402


@pytest.mark.slow
class TestCrossProcessAssembly:
    def test_balancer_assembles_replica_and_shard_fragments(
            self, remote_event_server, traces, monkeypatch):  # noqa: F811
        """The PR-4 three-process propagation tree, reproduced through
        the balancer's live ``GET /traces/<id>``: client → balancer →
        replica → fleet storage wire → event-shard process, ONE
        trace_id, remote spans parented under local ones."""
        import numpy as np

        from predictionio_tpu.controller import ComputeContext
        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.data.store import LEventStore
        from predictionio_tpu.fleet.balancer import QueryFleet
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates import recommendation as rec_tpl
        from predictionio_tpu.workflow import ServerConfig, run_train
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig, new_engine_instance,
        )

        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        monkeypatch.setenv("PIO_SLO_POLL_SEC", "0")

        class LiveReadALS(rec_tpl.ALSAlgorithm):
            def predict(self, model, query):
                LEventStore.find_by_entity(
                    app_name="obsapp", entity_type="user",
                    entity_id=query.user, event_names=["rate"],
                    target_entity_type="item", timeout=10.0)
                return super().predict(model, query)

        cfg = storage.StorageConfig(
            sources={"SHARDS": {"type": "fleet",
                                "urls": remote_event_server,
                                "service_key": "trace-secret"},
                     "LOCAL": {"type": "memory"}},
            repositories={"EVENTDATA": "SHARDS", "METADATA": "LOCAL",
                          "MODELDATA": "LOCAL"})
        storage.reset(cfg)
        try:
            aid = storage.get_metadata_apps().insert(App(0, "obsapp"))
            le = storage.get_levents()
            le.init(aid)
            t0 = dt.datetime(2021, 1, 1, tzinfo=UTC)
            rng = np.random.default_rng(0)
            le.insert_batch(
                [Event(event="rate", entity_type="user",
                       entity_id=f"u{u}", target_entity_type="item",
                       target_entity_id=f"i{rng.integers(0, 10)}",
                       properties={"rating": float(rng.integers(1, 6))},
                       event_time=t0)
                 for u in range(12) for _ in range(6)], aid)

            engine = rec_tpl.engine_factory().copy(
                algorithm_class_map={"als": LiveReadALS})
            params = EngineParams(
                data_source_params=("", rec_tpl.DataSourceParams(
                    app_name="obsapp")),
                algorithm_params_list=[
                    ("als", ALSParams(rank=4, num_iterations=2,
                                      seed=0))])
            instance = new_engine_instance(
                WorkflowConfig(engine_factory="test:obs"), params)
            iid = run_train(engine, params, instance,
                            ctx=ComputeContext())
            assert iid is not None

            traces.reset()
            qf = QueryFleet(
                ServerConfig(ip="127.0.0.1", port=0,
                             engine_instance_id=iid),
                replicas=2, engine=engine).start(undeploy_stale=False)
            try:
                host, port = qf.address
                tid = "5e" * 16
                conn = http.client.HTTPConnection(host, port,
                                                  timeout=60)
                conn.request(
                    "POST", "/queries.json",
                    body=json.dumps({"user": "u1", "num": 3}),
                    headers={"Content-Type": "application/json",
                             "traceparent": f"00-{tid}-{'6d' * 8}-01"})
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 200
                conn.close()

                # the live read races the root-span flush (the
                # response is written before the handler span closes):
                # poll until the balancer leg lands
                rec, names = None, set()
                for _ in range(40):
                    rec = json.loads(urllib.request.urlopen(
                        f"http://{host}:{port}/traces/{tid}",
                        timeout=10).read())
                    names = {s["name"] for s in rec["spans"]}
                    if "balancer POST /queries.json" in names:
                        break
                    time.sleep(0.05)
                assert rec["traceId"] == tid
                # balancer leg
                assert "balancer POST /queries.json" in names
                # replica leg (same process, same fragment)
                assert "query POST /queries.json" in names
                assert "serve.predict" in names
                # storage wire leg
                assert "storage.fleet.find" in names or \
                    "storage.resthttp.find" in names
                # shard-process leg, merged in live over HTTP
                assert "event GET /storage/events.jsonl" in names
                assert "storage.jsonlfs.find" in names
                # two processes contributed spans
                assert len(set(rec["processes"])) >= 2
                # remote spans hang off local ones
                local_pid = os.getpid()
                local_ids = {s["spanId"] for s in rec["spans"]
                             if s.get("pid") == local_pid}
                remote_http = next(
                    s for s in rec["spans"]
                    if s["name"] == "event GET /storage/events.jsonl")
                assert remote_http["pid"] != local_pid
                assert remote_http["parentId"] in local_ids
                # the shard is a REMOTE member in the federated view
                stats = json.loads(urllib.request.urlopen(
                    f"http://{host}:{port}/stats.json",
                    timeout=10).read())
                members = {m["member"]: m
                           for m in stats["fleet"]["members"]}
                assert members["shard0"]["ok"] is True
                assert not members["shard0"].get("inProcess")
            finally:
                qf.stop()
        finally:
            storage.reset()
