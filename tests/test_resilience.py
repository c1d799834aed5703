"""Chaos suite: fault-tolerant storage wire + degradation-aware serving.

Deterministic fault injection (``PIO_FAULTS``, seeded/counted per rule)
drives the scenarios the resilience layer exists for:

- transient storage failures (connection refused, timeouts, 5xx, torn
  writes) are masked by retries — an ingest-then-read run under a
  >=10% fault schedule is byte-identical to the fault-free run;
- a killed-and-restarted event server loses ZERO acknowledged events
  (client-generated event ids + server-side retry dedup);
- a full event-store blackout degrades query serving (``degraded:
  true`` responses off the device factor store) instead of 500ing,
  and flips ``GET /healthz`` readiness on every server;
- the micro-batcher sheds overload with 503 + Retry-After instead of
  queueing forever, and the feedback loop drops (bounded) instead of
  delaying queries.
"""

import datetime as dt
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.controller import ComputeContext, EngineParams
from predictionio_tpu.data import storage
from predictionio_tpu.data.api import EventServer, EventServerConfig
from predictionio_tpu.data.event import Event, new_event_id
from predictionio_tpu.data.storage import StorageConfig, StorageRegistry
from predictionio_tpu.data.storage.base import AccessKey, App, StorageError
from predictionio_tpu.data.storage.jsonlfs import JsonlFsLEvents
from predictionio_tpu.data.storage.resthttp import RestLEvents, _Wire
from predictionio_tpu.utils import faults, metrics, resilience
from predictionio_tpu.workflow import QueryServer, ServerConfig, run_train
from predictionio_tpu.workflow.create_workflow import (
    WorkflowConfig,
    new_engine_instance,
)

pytestmark = pytest.mark.chaos

UTC = dt.timezone.utc
CTX = ComputeContext()
KEY = "chaos-wire-key"
T0 = dt.datetime(2022, 5, 1, tzinfo=UTC)

# fast-retry knobs: transient-masking stays on but backoffs are
# milliseconds, so chaos scenarios run in test time
FAST_RETRY_ENV = {
    "PIO_STORAGE_RETRIES": "3",
    "PIO_STORAGE_RETRY_BASE": "0.005",
    "PIO_STORAGE_RETRY_MAX": "0.02",
    "PIO_STORAGE_OP_DEADLINE": "20",
    "PIO_STORAGE_CONNECT_TIMEOUT": "1.0",
}


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Breakers and injectors are process-global: every test starts
    and ends pristine so one scenario's open breaker cannot leak."""
    faults.clear()
    resilience.reset_breakers()
    resilience.set_enabled(True)
    yield
    faults.clear()
    resilience.reset_breakers()
    resilience.set_enabled(True)


@pytest.fixture
def fast_retries(monkeypatch):
    for k, v in FAST_RETRY_ENV.items():
        monkeypatch.setenv(k, v)
    yield


def _event(i: int, uid: str = None, eid: str = None) -> Event:
    return Event(
        event="rate", entity_type="user", entity_id=uid or f"u{i % 7}",
        target_entity_type="item", target_entity_id=f"i{i % 11}",
        properties={"rating": float(i % 5 + 1)},
        event_time=T0 + dt.timedelta(seconds=i), event_id=eid)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_get(addr, path):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    status, headers = resp.status, dict(resp.headers)
    conn.close()
    return status, json.loads(body.decode("utf-8")), headers


# ---------------------------------------------------------------------------
# RetryPolicy units
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def _policy(self, **kw):
        import random

        delays = []
        kw.setdefault("rng", random.Random(42))
        kw.setdefault("sleep", delays.append)
        return resilience.RetryPolicy(**kw), delays

    def test_transient_masked_within_budget(self):
        policy, delays = self._policy(max_retries=3, base_delay=0.01)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            if len(calls) < 3:
                raise ConnectionRefusedError("flaky")
            return "ok"

        assert policy.run(fn) == "ok"
        assert calls == [0, 1, 2]
        assert len(delays) == 2

    def test_full_jitter_bounds(self):
        policy, _ = self._policy(base_delay=0.1, max_delay=1.0)
        for attempt in range(8):
            cap = min(1.0, 0.1 * 2 ** attempt)
            for _ in range(50):
                assert 0.0 <= policy.backoff(attempt) <= cap

    def test_retry_after_floors_backoff(self):
        policy, _ = self._policy(base_delay=0.001, max_delay=2.0)
        assert policy.backoff(0, floor=0.5) >= 0.5

    def test_retry_after_floors_past_max_delay(self):
        # Retry-After is the server's own pacing: it must floor the
        # backoff even beyond max_delay (which caps only OUR jitter
        # curve) — but a pathological header stays bounded
        policy, _ = self._policy(base_delay=0.001, max_delay=2.0)
        assert policy.backoff(0, floor=10.0) >= 10.0
        cap = resilience.RetryPolicy.RETRY_AFTER_CAP
        assert policy.backoff(0, floor=1e6) <= cap

    def test_permanent_never_retried(self):
        policy, _ = self._policy(max_retries=5)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            raise ValueError("your fault")

        with pytest.raises(ValueError):
            policy.run(fn)
        assert calls == [0]

    def test_ambiguous_needs_idempotency(self):
        policy, _ = self._policy(max_retries=5)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            raise TimeoutError("maybe executed")

        with pytest.raises(TimeoutError):
            policy.run(fn, idempotent=False)
        assert calls == [0], "a non-idempotent op must not replay an " \
                            "ambiguous failure"
        calls.clear()
        with pytest.raises(TimeoutError):
            policy.run(fn, idempotent=True)
        assert len(calls) == 6

    def test_safe_failures_retry_even_non_idempotent(self):
        policy, _ = self._policy(max_retries=2)
        calls = []

        def fn(attempt):
            calls.append(attempt)
            if len(calls) == 1:
                raise ConnectionRefusedError("never sent")
            return attempt

        assert policy.run(fn, idempotent=False) == 1

    def test_deadline_budget_stops_retries(self):
        fake_now = [0.0]
        policy = resilience.RetryPolicy(
            max_retries=50, base_delay=1.0, max_delay=1.0, deadline=2.5,
            sleep=lambda d: fake_now.__setitem__(0, fake_now[0] + d),
            clock=lambda: fake_now[0])
        calls = []

        def fn(attempt):
            calls.append(attempt)
            fake_now[0] += 1.0  # each attempt costs 1s
            raise ConnectionRefusedError("down hard")

        with pytest.raises(ConnectionRefusedError):
            policy.run(fn)
        assert len(calls) <= 4, "retries must stop at the deadline, " \
                                "not at max_retries=50"

    def test_classification_pins_and_defaults(self):
        class Pinned(RuntimeError):
            pio_retry_class = resilience.SAFE

        assert resilience.classify(Pinned()) == resilience.SAFE
        assert resilience.classify(
            ConnectionRefusedError()) == resilience.SAFE
        assert resilience.classify(TimeoutError()) == resilience.AMBIGUOUS
        assert resilience.classify(
            ConnectionResetError()) == resilience.AMBIGUOUS
        assert resilience.classify(
            FileNotFoundError()) == resilience.PERMANENT
        assert resilience.classify(ValueError()) == resilience.PERMANENT


# ---------------------------------------------------------------------------
# CircuitBreaker units (fake clock — no real waiting)
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _breaker(self, **kw):
        now = [0.0]
        kw.setdefault("failure_threshold", 3)
        kw.setdefault("reset_timeout", 10.0)
        br = resilience.CircuitBreaker("test-ep", clock=lambda: now[0],
                                       **kw)
        return br, now

    def test_opens_on_consecutive_failures_then_half_open_closes(self):
        br, now = self._breaker()
        for _ in range(3):
            br.before_call()
            br.record_failure(TimeoutError())
        assert br.state == resilience.OPEN
        with pytest.raises(resilience.CircuitOpenError):
            br.before_call()
        now[0] += 10.0  # reset timeout elapses -> one probe admitted
        br.before_call()
        assert br.state == resilience.HALF_OPEN
        with pytest.raises(resilience.CircuitOpenError):
            br.before_call()  # second concurrent probe refused
        br.record_success()
        assert br.state == resilience.CLOSED
        br.before_call()  # closed again: calls flow

    def test_probe_failure_reopens(self):
        br, now = self._breaker()
        for _ in range(3):
            br.record_failure(ConnectionRefusedError())
        now[0] += 10.0
        br.before_call()  # half-open probe
        br.record_failure(TimeoutError())
        assert br.state == resilience.OPEN
        with pytest.raises(resilience.CircuitOpenError):
            br.before_call()  # timer restarted

    def test_half_open_probe_4xx_closes_not_wedges(self):
        """A half-open probe answered with a CLIENT error proves the
        endpoint is reachable: the breaker must close (and release the
        probe slot), never wedge half-open forever."""
        br, now = self._breaker()
        for _ in range(3):
            br.record_failure(TimeoutError())
        now[0] += 10.0
        br.before_call()  # half-open probe goes out
        br.record_failure(ValueError("400 from a healthy endpoint"))
        assert br.state == resilience.CLOSED
        br.before_call()  # traffic flows again

    def test_lost_probe_slot_reclaimed_after_reset_timeout(self):
        """A probe that never records an outcome (its deferred-success
        find iterator was dropped mid-stream) must not wedge the slot:
        past reset_timeout the slot is presumed lost and a new probe
        is admitted."""
        br, now = self._breaker()
        for _ in range(3):
            br.record_failure(TimeoutError())
        now[0] += 10.0
        br.before_call()  # probe goes out... and is abandoned
        with pytest.raises(resilience.CircuitOpenError):
            br.before_call()  # slot held while the probe is live
        now[0] += 10.0  # probe presumed lost
        br.before_call()  # slot reclaimed: a fresh probe is admitted
        assert br.state == resilience.HALF_OPEN
        br.record_success()
        assert br.state == resilience.CLOSED

    def test_retry_in_reports_remaining_not_full_timeout(self):
        br, now = self._breaker()  # reset_timeout=10
        for _ in range(3):
            br.record_failure(TimeoutError())
        now[0] += 7.0
        assert br.retry_in == pytest.approx(3.0)
        now[0] += 10.0
        assert br.retry_in == 0.0

    def test_own_refusals_never_feed_the_breaker(self):
        br, _ = self._breaker()
        for _ in range(3):
            br.record_failure(TimeoutError())
        assert br.state == resilience.OPEN
        # recording our own fast-fail must neither close nor re-open
        br.record_failure(resilience.CircuitOpenError("ep", 1.0))
        assert br.state == resilience.OPEN

    def test_non_transient_failures_never_trip(self):
        br, _ = self._breaker()
        for _ in range(20):
            br.before_call()
            br.record_failure(ValueError("client bug"))
        assert br.state == resilience.CLOSED

    def test_error_rate_window_opens(self):
        br, _ = self._breaker(failure_threshold=1000, window=10,
                              error_rate=0.5, min_calls=10)
        # alternate fail/ok (failure FIRST: successes against a clean
        # window take the steady-state fast path and are not recorded):
        # consecutive never reaches 1000, but once the window holds
        # min_calls outcomes at a 50% failure rate, a failure opens it
        for i in range(11):
            if i % 2 == 0:
                br.record_failure(TimeoutError())
            else:
                br.record_success()
        assert br.state == resilience.OPEN

    def test_is_blocking_does_not_consume_probe(self):
        br, now = self._breaker()
        for _ in range(3):
            br.record_failure(TimeoutError())
        assert br.is_blocking
        now[0] += 10.0
        assert not br.is_blocking  # probe due, but NOT consumed
        br.before_call()           # the real call takes the probe slot
        assert br.state == resilience.HALF_OPEN

    def test_transitions_emit_metrics(self):
        resilience.breaker_for("metrics-ep").record_failure(TimeoutError())
        br = resilience.breaker_for("metrics-ep")
        for _ in range(10):
            br.record_failure(TimeoutError())
        assert br.state == resilience.OPEN
        assert metrics.CIRCUIT_STATE.value(endpoint="metrics-ep") == 1.0
        assert metrics.CIRCUIT_TRANSITIONS.value(
            endpoint="metrics-ep", to="open") >= 1


# ---------------------------------------------------------------------------
# Fault-spec grammar + determinism
# ---------------------------------------------------------------------------


class TestFaultSpec:
    def test_parse_rejects_garbage(self):
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("kind=tornado")
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("rate=0.5,every=2,kind=refuse")
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("bogus_key=1")

    def test_parse_rejects_bad_quantifiers(self):
        # every=0 would be a ZeroDivisionError deep inside a storage op
        # if it survived parsing; it must die loudly here instead
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("kind=refuse,every=0")
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("kind=refuse,every=-3")
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("kind=refuse,rate=1.5")
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector.parse("kind=refuse,rate=-0.1")

    def _decisions(self, spec, n=40, backend="sqlite", op="insert_batch"):
        inj = faults.FaultInjector.parse(spec)
        out = []
        for _ in range(n):
            try:
                d = inj.maybe_fault(backend, op)
                out.append("torn" if d is not None else ".")
            except faults.InjectedFault as e:
                out.append(type(e).__name__)
        return out

    def test_seeded_rate_replays_exactly(self):
        spec = "backend=sqlite,kind=refuse,rate=0.3,seed=11"
        assert self._decisions(spec) == self._decisions(spec)
        fired = [d for d in self._decisions(spec) if d != "."]
        assert fired, "a 30% rule must fire within 40 calls"

    def test_every_after_times(self):
        spec = "op=insert*,kind=timeout,every=3,after=2,times=2"
        got = self._decisions(spec, n=12)
        fired_at = [i for i, d in enumerate(got) if d != "."]
        assert fired_at == [4, 7]  # after 2 skips, every 3rd, twice

    def test_matchers_are_globs(self):
        inj = faults.FaultInjector.parse(
            "backend=jsonl*,op=find*,kind=error,every=1")
        assert inj.maybe_fault("sqlite", "find") is None  # no raise
        with pytest.raises(faults.InjectedServerError):
            inj.maybe_fault("jsonlfs", "find_columnar_blocks")

    def test_error_kind_carries_status_and_retry_after(self):
        inj = faults.FaultInjector.parse(
            "kind=error,every=1,status=503,retry_after=2.5")
        with pytest.raises(faults.InjectedServerError) as ei:
            inj.maybe_fault("any", "any")
        assert ei.value.status == 503
        assert ei.value.pio_retry_after == 2.5
        assert resilience.retry_after_hint(ei.value) == 2.5

    def test_slow_composes_once_with_other_kinds(self, monkeypatch):
        """A slow rule composed with a raising/torn rule sleeps its
        delay exactly ONCE per call."""
        sleeps = []
        monkeypatch.setattr(faults.time, "sleep", sleeps.append)
        inj = faults.FaultInjector.parse(
            "kind=slow,delay=0.2,every=1;kind=torn,every=1")
        d = inj.maybe_fault("sqlite", "insert_batch")
        assert d is not None  # torn directive delivered
        assert sleeps == [0.2]
        sleeps.clear()
        inj2 = faults.FaultInjector.parse(
            "kind=slow,delay=0.1,every=1;kind=refuse,every=1")
        with pytest.raises(faults.InjectedConnectionRefused):
            inj2.maybe_fault("sqlite", "get")
        assert sleeps == [0.1]

    def test_env_spec_activates_and_tracks_changes(self, monkeypatch):
        monkeypatch.setenv("PIO_FAULTS", "kind=refuse,every=1")
        with pytest.raises(faults.InjectedConnectionRefused):
            faults.maybe_fault("memory", "get")
        monkeypatch.setenv("PIO_FAULTS", "")
        assert faults.maybe_fault("memory", "get") is None


# ---------------------------------------------------------------------------
# DAO wrapper chaos: injected faults masked by retries (local backends)
# ---------------------------------------------------------------------------


class TestWrapperResilience:
    def test_lazy_find_failure_feeds_breaker(self, mem_storage):
        """find() on local lazy backends returns a generator: creating
        it proves nothing. The breaker's verdict must come from the
        ITERATION — a backend dying mid-scan counts as a failed read,
        and mere generator creation must not keep resetting the
        consecutive-failure count."""
        from predictionio_tpu.data.storage.observed import (
            DAOMetricsWrapper,
        )

        class _DyingScan:
            metrics_backend = "dying"

            @staticmethod
            def find(app_id, channel_id=None, **kw):
                yield _event(1)
                raise TimeoutError("disk fell over mid-scan")

        resilience.reset_breakers()
        dao = DAOMetricsWrapper(_DyingScan(), backend="dying")
        br = resilience.breaker_for("dying")
        # creating (and abandoning) generators is breaker-neutral
        for _ in range(3):
            dao.find(1)
        assert br.state == "closed" and br._consecutive == 0
        for _ in range(br.failure_threshold):
            with pytest.raises(TimeoutError):
                list(dao.find(1))
        assert br.state == "open", \
            "mid-iteration failures must trip the breaker even though " \
            "every generator CREATION succeeded"

    def test_storage_ready_swallows_resolution_failure(self):
        def boom():
            raise RuntimeError("storage not configured")

        assert resilience.storage_ready(boom) is False

    def test_transients_masked_exactly_once_sqlite(self, fast_retries,
                                                   sqlite_storage):
        # >=10% injected transients across ALL sqlite ops: refusals
        # (safe), timeouts (ambiguous, retried because sqlite inserts
        # are id-keyed upserts), one torn write (half the batch lands,
        # then the retry replays the full batch idempotently)
        torn_before = metrics.FAULTS_INJECTED.value(
            backend="sqlite", op="insert_batch", kind="torn")
        faults.install(
            "backend=sqlite,kind=refuse,every=4,seed=3;"
            "backend=sqlite,op=insert_batch,kind=timeout,every=5;"
            "backend=sqlite,op=insert_batch,kind=torn,after=2,times=1")
        le = storage.get_levents()
        le.init(1)
        sent = []
        for b in range(12):
            evs = [_event(b * 5 + j, eid=new_event_id()) for j in range(5)]
            sent.extend(e.event_id for e in evs)
            le.insert_batch(evs, 1)
        got = [e.event_id for e in le.find(app_id=1)]
        assert sorted(got) == sorted(sent), \
            "retries must mask every injected transient with no loss " \
            "and no duplication"
        assert metrics.FAULTS_INJECTED.value(
            backend="sqlite", op="insert_batch",
            kind="torn") == torn_before + 1
        assert metrics.STORAGE_RETRIES.value(
            backend="sqlite", op="insert_batch") > 0

    def test_reads_masked_memory(self, fast_retries, mem_storage):
        le = storage.get_levents()
        le.init(1)
        ids = le.insert_batch([_event(i) for i in range(10)], 1)
        faults.install("backend=memory,op=get,kind=timeout,every=2")
        for eid in ids:
            assert le.get(eid, 1) is not None, \
                "every 2nd get times out; retries must mask all of them"

    def test_persistent_failure_opens_breaker_fast_fail(
            self, fast_retries, mem_storage, monkeypatch):
        monkeypatch.setenv("PIO_STORAGE_RETRIES", "0")
        storage.reset(StorageConfig(
            sources={"TEST": {"type": "memory"}},
            repositories={"METADATA": "TEST", "EVENTDATA": "TEST",
                          "MODELDATA": "TEST"}))
        le = storage.get_levents()
        le.init(1)
        faults.install("backend=memory,op=get,kind=refuse,every=1")
        for _ in range(6):
            with pytest.raises(Exception):
                le.get("nope", 1)
        br = resilience.breaker_for("memory")
        assert br.state == resilience.OPEN
        t0 = time.perf_counter()
        with pytest.raises(resilience.CircuitOpenError):
            le.get("nope", 1)
        assert time.perf_counter() - t0 < 0.05, \
            "an open breaker must fail in microseconds, not timeouts"
        # non-event-store DAO traffic (init on another app) also gated
        with pytest.raises(resilience.CircuitOpenError):
            le.init(2)

    def test_hung_store_trips_breaker_via_read_deadline(
            self, mem_storage):
        """A WEDGED backend (blocks, never raises) is invisible to the
        DAO-level failure accounting — the predict-read deadline must
        feed the breaker so later reads fast-fail instead of each
        paying the full timeout."""
        from predictionio_tpu.data.store import LEventStore, \
            LEventStoreTimeoutError

        storage.get_metadata_apps().insert(App(0, "hungapp"))
        le = storage.get_levents()
        le.init(1)
        wedge = threading.Event()
        real_find = le._wrapped.find

        def hung_find(*a, **k):
            wedge.wait(3)
            return real_find(*a, **k)

        le._wrapped.find = hung_find
        try:
            br = resilience.breaker_for("memory")
            for _ in range(br.failure_threshold):
                with pytest.raises(LEventStoreTimeoutError):
                    LEventStore.find_by_entity(
                        app_name="hungapp", entity_type="user",
                        entity_id="u", timeout=0.05)
            assert br.state == resilience.OPEN
            # the wedged store now costs microseconds, not the timeout
            t0 = time.perf_counter()
            with pytest.raises(resilience.CircuitOpenError):
                LEventStore.find_by_entity(
                    app_name="hungapp", entity_type="user",
                    entity_id="u", timeout=0.05)
            assert time.perf_counter() - t0 < 0.04
        finally:
            wedge.set()
            le._wrapped.find = real_find

    def test_kill_switch_bypasses_layer(self, mem_storage):
        resilience.set_enabled(False)
        faults.install("backend=memory,op=get,kind=refuse,every=1")
        le = storage.get_levents()
        le.init(1)
        # faults still fire (the injector is independent of the
        # retry/breaker switch) but nothing retries or trips breakers
        with pytest.raises(ConnectionRefusedError):
            le.get("x", 1)
        assert resilience.breaker_for("memory").state == resilience.CLOSED

    def test_kill_switch_bypasses_bounded_breaker(self, mem_storage):
        """PIO_RESILIENCE=0 must bypass the predict-read breaker too:
        an open breaker neither blocks reads nor accumulates state
        from deadline timeouts while the layer is off."""
        from predictionio_tpu.data.store import LEventStore

        storage.get_metadata_apps().insert(App(0, "killapp"))
        storage.get_levents().init(1)
        br = resilience.breaker_for("memory")
        for _ in range(br.failure_threshold):
            br.record_failure(TimeoutError())
        assert br.state == resilience.OPEN
        resilience.set_enabled(False)
        # reads pass straight through the open breaker
        assert LEventStore.find_by_entity(
            app_name="killapp", entity_type="user", entity_id="u",
            timeout=1.0) == []


# ---------------------------------------------------------------------------
# Wire: split timeouts + retried-POST dedup
# ---------------------------------------------------------------------------


class TestWireConfig:
    def test_split_timeout_defaults_and_legacy(self, monkeypatch):
        monkeypatch.delenv("PIO_STORAGE_CONNECT_TIMEOUT", raising=False)
        monkeypatch.delenv("PIO_STORAGE_READ_TIMEOUT", raising=False)
        w = _Wire({"url": "http://h:1"})
        assert w.connect_timeout == 3.0, \
            "connects must default far below the old flat 60s"
        assert w.read_timeout == 60.0
        # legacy flat `timeout` config keeps meaning the READ timeout
        assert _Wire({"url": "http://h:1",
                      "timeout": "7"}).read_timeout == 7.0

    def test_env_and_config_overrides(self, monkeypatch):
        monkeypatch.setenv("PIO_STORAGE_CONNECT_TIMEOUT", "0.5")
        monkeypatch.setenv("PIO_STORAGE_READ_TIMEOUT", "9")
        w = _Wire({"url": "http://h:1"})
        assert (w.connect_timeout, w.read_timeout) == (0.5, 9.0)
        w2 = _Wire({"url": "http://h:1", "connect_timeout": "0.25",
                    "read_timeout": "4"})
        assert (w2.connect_timeout, w2.read_timeout) == (0.25, 4.0)

    def test_default_deadline_survives_a_read_stall(self, monkeypatch):
        # with the old flat 30s budget a 60s read timeout consumed the
        # whole budget in one attempt: timeout-class failures could
        # never actually retry under default config
        monkeypatch.delenv("PIO_STORAGE_OP_DEADLINE", raising=False)
        monkeypatch.delenv("PIO_STORAGE_READ_TIMEOUT", raising=False)
        w = _Wire({"url": "http://h:1"})
        assert w.policy.deadline > w.read_timeout + w.policy.max_delay
        # an explicit operator-set budget still wins
        monkeypatch.setenv("PIO_STORAGE_OP_DEADLINE", "12")
        assert _Wire({"url": "http://h:1"}).policy.deadline == 12.0

    def test_retry_header_only_after_ambiguous_failure(self):
        # a SAFE failure (connect refused) provably never executed:
        # flagging its retry as a possible replay lets the server's
        # byte-digest cache swallow a legitimate id-less append whose
        # bytes match an earlier committed one. Only an AMBIGUOUS
        # failure (may have committed) earns X-Idempotency-Retry.
        from predictionio_tpu.data.storage.resthttp import (
            StorageTimeout,
            StorageUnavailable,
        )

        class _Resp:
            status = 200
            headers = {}

            @staticmethod
            def read():
                return b'{"count": 1}'

        class _Conn:
            @staticmethod
            def close():
                pass

        def run_with(first_error):
            w = _Wire({"url": "http://h:1"})
            w.policy = resilience.RetryPolicy(
                max_retries=2, base_delay=0.0, max_delay=0.0)
            seen = []
            calls = [0]

            def fake_request_once(method, pathq, body, headers):
                seen.append(headers)
                calls[0] += 1
                if calls[0] == 1:
                    raise first_error
                return _Conn, _Resp

            w._request_once = fake_request_once
            w.call("POST", "/storage/events.jsonl", {}, body=b"x")
            return seen

        safe = run_with(StorageUnavailable(
            "refused", retry_class=resilience.SAFE))
        assert len(safe) == 2
        assert "X-Idempotency-Retry" not in safe[1], \
            "a SAFE retry must not flag itself as a possible replay"
        ambiguous = run_with(StorageTimeout("stalled"))
        assert len(ambiguous) == 2
        assert ambiguous[1].get("X-Idempotency-Retry") == "1"

    def test_get_redirects_followed_same_origin_only(self):
        # the old urllib lane followed GET redirects (gateway
        # trailing-slash canonicalization); the http.client rewrite
        # must not regress that — but an off-origin Location is a
        # config error, not something to silently re-dial
        class _Resp:
            def __init__(self, status, headers=None, body=b'{"n": 1}'):
                self.status = status
                self.headers = headers or {}
                self._body = body

            def read(self, *a):
                return self._body

        class _Conn:
            @staticmethod
            def close():
                pass

        def make_wire(responses):
            w = _Wire({"url": "http://h:1"})
            w.policy = resilience.RetryPolicy(max_retries=0)
            paths = []

            def fake(method, pathq, body, headers):
                paths.append(pathq)
                return _Conn, responses.pop(0)

            w._request_once = fake
            return w, paths

        w, paths = make_wire([
            _Resp(302, {"Location": "http://h:1/storage/init.json/?x=1"}),
            _Resp(200)])
        status, payload = w.call("GET", "/storage/init.json", {})
        assert status == 200 and payload == {"n": 1}
        assert paths[1] == "/storage/init.json/?x=1"

        w, _ = make_wire([_Resp(301,
                                {"Location": "https://other:9/whatever"})])
        with pytest.raises(StorageError, match="off-origin"):
            w.call("GET", "/storage/init.json", {})

        # a write is NEVER redirected: the 3xx surfaces as an error
        w, paths = make_wire([_Resp(301, {"Location": "http://h:1/x"})])
        with pytest.raises(StorageError, match="301"):
            w.call("POST", "/storage/events.jsonl", {}, body=b"x")
        assert len(paths) == 1

    def test_reverse_proxy_path_prefix_preserved(self):
        w = _Wire({"url": "http://gw.example.com/pio-events/"})
        assert w._full("/storage/events.jsonl", {"appId": 1}).startswith(
            "/pio-events/storage/events.jsonl?")
        assert _Wire({"url": "http://h:1"})._full(
            "/storage/init.json", {}).startswith("/storage/init.json?")

    def test_unreachable_fails_fast_and_safe(self, fast_retries):
        port = _free_port()
        le = RestLEvents({"url": f"http://127.0.0.1:{port}"})
        t0 = time.perf_counter()
        with pytest.raises(StorageError, match="unreachable"):
            le.init(1)
        # 4 connect-refused attempts + ms backoffs, nowhere near 60s
        assert time.perf_counter() - t0 < 2.0

    def test_pooled_conn_failure_phase_decides_redial_vs_ambiguous(self):
        """A reused keep-alive socket is only provably stale until the
        send completes: a SEND failure redials (the server closed the
        idle socket — nothing executed), but a failure waiting for the
        RESPONSE means the server may already have committed. That must
        surface AMBIGUOUS like the fresh-dial path — a silent re-send
        would bypass idempotent=False (unkeyed append twice, a
        committed delete replayed)."""
        from predictionio_tpu.data.storage.resthttp import (
            StorageUnavailable,
        )

        class _FakePooled:
            def __init__(self, fail_at):
                self.fail_at = fail_at
                self.closed = False

            def request(self, *a, **k):
                if self.fail_at == "send":
                    raise BrokenPipeError("idle socket closed")

            def getresponse(self):
                raise ConnectionResetError("reset before response")

            def close(self):
                self.closed = True

        def wire_with(fail_at):
            w = _Wire({"url": "http://h:1"})
            pooled = _FakePooled(fail_at)
            w._checkout = lambda: pooled
            dials = []

            def fake_dial():
                dials.append(1)
                raise StorageUnavailable(
                    "refused", retry_class=resilience.SAFE)

            w._dial = fake_dial
            return w, pooled, dials

        # response-phase failure: AMBIGUOUS raise, NO silent redial
        w, pooled, dials = wire_with("response")
        with pytest.raises(StorageUnavailable) as ei:
            w._request_once("POST", "/x", b"b", {})
        assert resilience.classify(ei.value) == resilience.AMBIGUOUS
        assert pooled.closed
        assert not dials, \
            "a dropped response on a reused conn must never re-send"

        # send-phase failure: the classic stale keep-alive — redial
        w, pooled, dials = wire_with("send")
        with pytest.raises(StorageUnavailable) as ei:
            w._request_once("POST", "/x", b"b", {})
        assert pooled.closed and dials
        assert resilience.classify(ei.value) == resilience.SAFE


def _inproc_event_server(reg_cfg: StorageConfig):
    reg = StorageRegistry(reg_cfg)
    es = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                       service_key=KEY), reg=reg).start()
    return es, f"http://{es.address[0]}:{es.address[1]}"


def _jsonlfs_reg_cfg(tmp_path) -> StorageConfig:
    return StorageConfig(
        sources={"EV": {"type": "jsonlfs", "path": str(tmp_path / "ev"),
                        "part_max_events": "32"},
                 "META": {"type": "memory"}},
        repositories={"EVENTDATA": "EV", "METADATA": "META",
                      "MODELDATA": "META"})


class TestWireChaosDifferential:
    """Acceptance: a seeded schedule injecting >=10% transient wire
    failures produces a store byte-identical to the fault-free run."""

    @staticmethod
    def _ingest(client: RestLEvents, app_id: int, batches):
        for evs in batches:
            client.insert_batch(evs, app_id)

    def test_ingest_byte_identical_under_faults(self, fast_retries,
                                                tmp_path):
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            client = RestLEvents({"url": url, "service_key": KEY})
            # ONE set of event objects (ids, creationTime and all)
            # ingested into two apps: the lanes must end byte-identical
            batches = [[_event(b * 6 + j, eid=new_event_id())
                        for j in range(6)] for b in range(10)]
            client.init(1)
            client.init(2)
            self._ingest(client, 1, batches)  # clean reference lane
            # every=N schedules: deterministic, >=10% of wire calls
            # fail (refuse = never sent; timeout = ambiguous; torn =
            # server committed but the response was lost, so the
            # retried POST must dedup server-side on jsonlfs)
            faults.install(
                "backend=resthttp,kind=refuse,every=3,seed=1;"
                "backend=resthttp,op=insert_batch,kind=timeout,every=4;"
                "backend=resthttp,op=insert_batch,kind=torn,every=5")
            self._ingest(client, 2, batches)
            faults.clear()
            clean = sorted(e.to_json() for e in client.find(app_id=1))
            chaos = sorted(e.to_json() for e in client.find(app_id=2))
            # same ids, same payloads -> identical JSON except the two
            # lanes' appId never appears in event JSON; compare bytes
            assert chaos == clean, \
                "faulted ingest must be byte-identical to fault-free " \
                "(zero acknowledged-event loss, zero duplication)"
            assert metrics.STORAGE_RETRIES.value(
                backend="resthttp", op="insert_batch") > 0
        finally:
            es.stop()

    def test_reads_byte_identical_under_faults(self, fast_retries,
                                               tmp_path):
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            client = RestLEvents({"url": url, "service_key": KEY})
            client.init(1)
            ids = [new_event_id() for _ in range(40)]
            client.insert_batch(
                [_event(i, eid=ids[i]) for i in range(40)], 1)
            clean = sorted(e.to_json() for e in client.find(app_id=1))
            one = client.get(ids[0], 1)
            faults.install("backend=resthttp,kind=refuse,every=2;"
                           "backend=resthttp,op=get,kind=timeout,every=3")
            chaos = sorted(e.to_json() for e in client.find(app_id=1))
            assert chaos == clean
            assert client.get(ids[0], 1).to_json() == one.to_json()
            faults.clear()
            # a torn rule on a STREAM op manifests (response lost after
            # the server answered) and is masked by the stream retry
            before = metrics.FAULTS_INJECTED.value(
                backend="resthttp", op="find", kind="torn")
            faults.install("backend=resthttp,op=find,kind=torn,times=1")
            assert sorted(e.to_json()
                          for e in client.find(app_id=1)) == clean
            assert metrics.FAULTS_INJECTED.value(
                backend="resthttp", op="find",
                kind="torn") == before + 1
        finally:
            es.stop()


class TestKilledServerZeroLoss:
    """Acceptance: kill -9 the event server mid-ingest, restart it, and
    every ACKNOWLEDGED batch is present exactly once — wire retries
    (same client-generated ids + X-Idempotency-Retry dedup) span the
    outage."""

    def _spawn(self, port: int, store: str):
        env = dict(os.environ)
        env.update({
            "PIO_STORAGE_SOURCES_EV_TYPE": "jsonlfs",
            "PIO_STORAGE_SOURCES_EV_PATH": store,
            "PIO_STORAGE_SOURCES_EV_PART_MAX_EVENTS": "32",
            "PIO_STORAGE_SOURCES_META_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "META",
            "JAX_PLATFORMS": "cpu",
        })
        return subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.tools.console",
             "eventserver", "--ip", "127.0.0.1", "--port", str(port),
             "--service-key", KEY],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    @staticmethod
    def _wait_ready(proc, url, timeout=60):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(url + "/", timeout=1):
                    return
            except Exception:
                if proc.poll() is not None:
                    raise RuntimeError(
                        "eventserver died:\n"
                        + proc.stdout.read().decode())
                time.sleep(0.1)
        raise RuntimeError("eventserver never became ready")

    def test_mid_ingest_kill_restart_no_acked_loss(self, tmp_path,
                                                   monkeypatch):
        # the retry budget must SPAN the restart window (console
        # startup is seconds): many cheap attempts, generous deadline
        monkeypatch.setenv("PIO_STORAGE_RETRIES", "120")
        monkeypatch.setenv("PIO_STORAGE_RETRY_BASE", "0.2")
        monkeypatch.setenv("PIO_STORAGE_RETRY_MAX", "0.5")
        monkeypatch.setenv("PIO_STORAGE_OP_DEADLINE", "90")
        monkeypatch.setenv("PIO_STORAGE_CONNECT_TIMEOUT", "1.0")
        store = str(tmp_path / "killstore")
        port = _free_port()
        url = f"http://127.0.0.1:{port}"
        proc = self._spawn(port, store)
        proc2 = None
        try:
            self._wait_ready(proc, url)
            client = RestLEvents({"url": url, "service_key": KEY})
            client.init(1)
            n_batches, per = 20, 10
            acked = []
            restarted = {}

            def restart_later():
                time.sleep(1.0)
                restarted["proc"] = self._spawn(port, store)

            rt = None
            for b in range(n_batches):
                evs = [_event(b * per + j, eid=new_event_id())
                       for j in range(per)]
                if b == n_batches // 2:
                    # crash NOW: this batch (and followers) hit a dead
                    # server; the wire retries until the restart —
                    # running concurrently — brings it back
                    proc.send_signal(signal.SIGKILL)
                    proc.wait()
                    rt = threading.Thread(target=restart_later,
                                          daemon=True)
                    rt.start()
                ids = client.insert_batch(evs, 1)
                acked.extend(ids)
            assert rt is not None
            rt.join(70)
            proc2 = restarted.get("proc")
            assert proc2 is not None, "restart thread never ran"
            got = [e.event_id for e in client.find(app_id=1)]
            assert len(acked) == n_batches * per
            assert sorted(got) == sorted(acked), \
                "acknowledged events must survive a kill -9 exactly " \
                "once (no loss, no retry duplication)"
        finally:
            for p in (proc, proc2):
                if p is not None and p.poll() is None:
                    p.terminate()
                    p.wait(timeout=10)


class TestRawAppendIdempotency:
    """Id-less raw lines carry no idempotency key: an ambiguous wire
    failure must NOT be retried for them (a committed first attempt
    would be undedupable), while keyed lines retry and dedup."""

    def test_idless_lines_fail_fast_keyed_lines_retry(
            self, fast_retries, tmp_path):
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            client = RestLEvents({"url": url, "service_key": KEY})
            client.init(1)
            noid = [json.dumps({"event": "rate", "entityType": "user",
                                "entityId": "u1",
                                "targetEntityType": "item",
                                "targetEntityId": "i1",
                                "eventTime":
                                    "2022-05-01T00:00:00+00:00"})]
            faults.install("backend=resthttp,op=append_raw_lines,"
                           "kind=timeout,times=1")
            with pytest.raises(TimeoutError):
                client.append_raw_lines(noid, 1)
            faults.clear()
            assert list(client.find(app_id=1)) == []
            keyed = [_event(i, eid=new_event_id()).to_json()
                     for i in range(3)]
            faults.install("backend=resthttp,op=append_raw_lines,"
                           "kind=timeout,times=1")
            client.append_raw_lines(keyed, 1)  # one fault, masked
            faults.clear()
            assert len(list(client.find(app_id=1))) == 3
        finally:
            es.stop()


class TestRetriedAppendDedup:
    def test_retry_header_dedups_committed_lines_jsonlfs(self, tmp_path):
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            wire = _Wire({"url": url, "service_key": KEY})
            lines = [_event(i, eid=new_event_id()).to_json()
                     for i in range(5)]
            body = "\n".join(lines).encode("utf-8")
            wire.call("POST", "/storage/events.jsonl", {"appId": 1},
                      body=body, op="append_raw_lines")
            # the "response was lost" replay: same body, retry header
            import http.client as hc

            conn = hc.HTTPConnection(*es.address, timeout=10)
            conn.request("POST",
                         wire._full("/storage/events.jsonl",
                                    {"appId": 1}),
                         body=body,
                         headers={"X-Idempotency-Retry": "1",
                                  "Content-Type":
                                      "application/x-jsonlines"})
            assert conn.getresponse().status == 200
            conn.close()
            client = RestLEvents({"url": url, "service_key": KEY})
            got = [e.event_id for e in client.find(app_id=1)]
            assert len(got) == 5 and len(set(got)) == 5, \
                "a retried append must not duplicate committed events"
            # a blind re-POST without the header DOES append (the scan
            # only runs on declared retries)
            wire.call("POST", "/storage/events.jsonl", {"appId": 1},
                      body=body, op="append_raw_lines")
            assert len(list(client.find(app_id=1))) == 10
        finally:
            es.stop()

    @staticmethod
    def _retried_post(es, wire, body: bytes) -> int:
        import http.client as hc

        conn = hc.HTTPConnection(*es.address, timeout=10)
        try:
            conn.request("POST",
                         wire._full("/storage/events.jsonl", {"appId": 1}),
                         body=body,
                         headers={"X-Idempotency-Retry": "1",
                                  "Content-Type":
                                      "application/x-jsonlines"})
            resp = conn.getresponse()
            assert resp.status == 200
            return json.loads(resp.read())["count"]
        finally:
            conn.close()

    def test_replay_hit_answers_without_existence_scan(self, tmp_path):
        """A retried POST whose bytes match a committed append is a
        pure replay: answered from the digest cache in O(hash), never
        rescanning the store (the scan is O(store) on jsonlfs). Only a
        miss — unknown body, e.g. after a server restart — pays it."""
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            wire = _Wire({"url": url, "service_key": KEY})
            lines = [_event(i, eid=new_event_id()).to_json()
                     for i in range(4)]
            body = "\n".join(lines).encode("utf-8")
            wire.call("POST", "/storage/events.jsonl", {"appId": 1},
                      body=body, op="append_raw_lines")
            scans = []
            orig = es._dedup_retried_lines
            es._dedup_retried_lines = \
                lambda *a, **k: (scans.append(1), orig(*a, **k))[1]
            assert self._retried_post(es, wire, body) == 4
            assert scans == [], \
                "byte-identical replay must skip the existence scan"
            client = RestLEvents({"url": url, "service_key": KEY})
            assert len(list(client.find(app_id=1))) == 4
            # an unknown retried body (nothing committed) misses the
            # cache, pays the scan once, and still appends exactly once
            fresh = _event(99, eid=new_event_id()).to_json()
            assert self._retried_post(
                es, wire, fresh.encode("utf-8")) == 1
            assert scans == [1]
            assert len(list(client.find(app_id=1))) == 5
        finally:
            es.stop()

    def test_scan_path_acks_full_count(self, tmp_path):
        """A retried append whose every line is already committed must
        ack the request's FULL line count even when the replay cache is
        gone (server restart): the body IS durable — acking the
        post-dedup remainder (0) would tell the client its committed
        append was lost."""
        es, url = _inproc_event_server(_jsonlfs_reg_cfg(tmp_path))
        try:
            wire = _Wire({"url": url, "service_key": KEY})
            lines = [_event(i, eid=new_event_id()).to_json()
                     for i in range(3)]
            body = "\n".join(lines).encode("utf-8")
            wire.call("POST", "/storage/events.jsonl", {"appId": 1},
                      body=body, op="append_raw_lines")
            with es._append_seen_lock:  # simulate a restarted server
                es._append_seen.clear()
            assert self._retried_post(es, wire, body) == 3, \
                "cache miss + full dedup must ack like the cache hit"
            client = RestLEvents({"url": url, "service_key": KEY})
            assert len(list(client.find(app_id=1))) == 3
        finally:
            es.stop()


# ---------------------------------------------------------------------------
# Torn-write crash recovery (sqlite + jsonlfs) — satellite
# ---------------------------------------------------------------------------


class TestTornWriteRecovery:
    def test_jsonlfs_torn_tail_reopen_readable(self, tmp_path):
        path = str(tmp_path / "torn")
        le = JsonlFsLEvents({"path": path, "part_max_events": 8})
        le.init(1)
        ids = le.insert_batch([_event(i) for i in range(5)], 1)
        # crash mid-append: a truncated JSON fragment with no newline
        # lands at the tail of the last partition
        d = le._dir(1, None)
        part = le._parts(d)[-1]
        with open(part, "ab") as f:
            f.write(b'{"event":"rate","entityType":"user","entityI')
        fresh = JsonlFsLEvents({"path": path, "part_max_events": 8})
        got = [e.event_id for e in fresh.find(app_id=1)]
        assert sorted(got) == sorted(ids), \
            "reopen after a torn append: committed events only, no " \
            "phantom event from the fragment"
        # the next append must not glue onto the fragment
        new_ids = fresh.insert_batch([_event(100)], 1)
        got2 = [e.event_id for e in fresh.find(app_id=1)]
        assert sorted(got2) == sorted(ids + new_ids)

    def test_jsonlfs_torn_multibyte_tail(self, tmp_path):
        path = str(tmp_path / "torn_mb")
        le = JsonlFsLEvents({"path": path})
        le.init(1)
        ids = le.insert_batch([_event(i) for i in range(3)], 1)
        part = le._parts(le._dir(1, None))[-1]
        with open(part, "ab") as f:
            # fragment cut mid-multibyte character
            f.write('{"event":"rate","entityId":"日本'.encode("utf-8")[:-1])
        fresh = JsonlFsLEvents({"path": path})
        assert sorted(e.event_id for e in fresh.find(app_id=1)) \
            == sorted(ids)

    def test_sqlite_torn_batch_retry_exactly_once(self, fast_retries,
                                                  sqlite_storage,
                                                  tmp_path):
        # DAO-level torn write: half the batch commits, the op fails
        # ambiguously, the retry replays the full batch — sqlite's
        # id-keyed INSERT OR REPLACE makes the replay exact
        faults.install(
            "backend=sqlite,op=insert_batch,kind=torn,times=1")
        le = storage.get_levents()
        le.init(1)
        evs = [_event(i, eid=new_event_id()) for i in range(8)]
        le.insert_batch(evs, 1)
        got = [e.event_id for e in le.find(app_id=1)]
        assert sorted(got) == sorted(e.event_id for e in evs)
        # reopen the database file cold: still consistent
        db_path = sqlite_storage.config.sources["TEST"]["path"]
        storage.reset(StorageConfig(
            sources={"TEST": {"type": "sqlite", "path": db_path}},
            repositories={"METADATA": "TEST", "EVENTDATA": "TEST",
                          "MODELDATA": "TEST"}))
        got2 = [e.event_id for e in storage.get_levents().find(app_id=1)]
        assert sorted(got2) == sorted(e.event_id for e in evs)

    def test_sqlite_no_retry_leaves_no_phantom_duplicates(
            self, fast_retries, sqlite_storage, monkeypatch):
        # even with retries OFF a torn write must leave a readable
        # store whose events are a PREFIX of the batch (no corruption)
        monkeypatch.setenv("PIO_STORAGE_RETRIES", "0")
        storage.reset(StorageConfig(
            sources={"TEST": {"type": "sqlite",
                              "path": sqlite_storage.config
                              .sources["TEST"]["path"]}},
            repositories={"METADATA": "TEST", "EVENTDATA": "TEST",
                          "MODELDATA": "TEST"}))
        faults.install(
            "backend=sqlite,op=insert_batch,kind=torn,times=1")
        le = storage.get_levents()
        le.init(1)
        evs = [_event(i, eid=new_event_id()) for i in range(8)]
        with pytest.raises(faults.InjectedTornWrite):
            le.insert_batch(evs, 1)
        got = {e.event_id for e in le.find(app_id=1)}
        assert got.issubset({e.event_id for e in evs})
        assert len(got) == len(set(got))


# ---------------------------------------------------------------------------
# Degradation-aware serving: blackout keeps answering
# ---------------------------------------------------------------------------

ECOMM_FACTORY = ("predictionio_tpu.templates.ecommercerecommendation:"
                 "engine_factory")


def _seed_ecomm(app_id: int) -> None:
    le = storage.get_levents()
    le.init(app_id)
    rng = np.random.default_rng(3)
    evs = []
    for u in range(12):
        evs.append(Event(event="$set", entity_type="user",
                         entity_id=f"u{u}", event_time=T0))
    for i in range(15):
        evs.append(Event(event="$set", entity_type="item",
                         entity_id=f"i{i}",
                         properties={"categories": ["c1"]},
                         event_time=T0))
    for u in range(12):
        for _ in range(6):
            evs.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{rng.integers(0, 15)}",
                event_time=T0 + dt.timedelta(seconds=int(u))))
    le.insert_batch(evs, app_id)


def _train_ecomm() -> str:
    from predictionio_tpu.templates.ecommercerecommendation import (
        DataSourceParams as EDSP,
        ECommAlgorithmParams,
        engine_factory,
    )

    engine = engine_factory()
    params = EngineParams(
        data_source_params=("", EDSP(app_name="ecomm")),
        algorithm_params_list=[
            ("als", ECommAlgorithmParams(
                app_name="ecomm", unseen_only=True, rank=4,
                num_iterations=3, seed=1))],
    )
    instance = new_engine_instance(
        WorkflowConfig(engine_factory=ECOMM_FACTORY), params)
    iid = run_train(engine, params, instance, ctx=CTX)
    assert iid is not None
    return iid


@pytest.fixture
def ecomm_stack(fast_retries, tmp_path):
    """Ecommerce deployment whose EVENTDATA is a live in-process event
    server over the resthttp wire — the serve-time constraint reads
    (seen items, unavailable items, weights) cross the network, so
    stopping the server IS an event-store blackout."""
    es, url = _inproc_event_server(StorageConfig(
        sources={"S": {"type": "memory"}},
        repositories={"EVENTDATA": "S", "METADATA": "S",
                      "MODELDATA": "S"}))
    storage.reset(StorageConfig(
        sources={"EV": {"type": "resthttp", "url": url,
                        "service_key": KEY},
                 "LOCAL": {"type": "memory"}},
        repositories={"EVENTDATA": "EV", "METADATA": "LOCAL",
                      "MODELDATA": "LOCAL"}))
    aid = storage.get_metadata_apps().insert(App(0, "ecomm"))
    _seed_ecomm(aid)
    iid = _train_ecomm()
    srv = QueryServer(ServerConfig(engine_instance_id=iid)).deploy()
    yield {"es": es, "srv": srv, "url": url, "app_id": aid}
    storage.reset()
    es.stop()


class TestDegradedServing:
    def _query(self, srv, user="u1"):
        return srv.handle_query(
            json.dumps({"user": user, "num": 3}).encode("utf-8"))

    def test_healthy_serving_not_degraded(self, ecomm_stack):
        status, result = self._query(ecomm_stack["srv"])
        assert status == 200
        assert "degraded" not in result
        assert "itemScores" in result

    def test_serve_byte_identical_under_transient_faults(self,
                                                         ecomm_stack):
        srv = ecomm_stack["srv"]
        users = [f"u{i % 12}" for i in range(12)]
        clean = [self._query(srv, u) for u in users]
        faults.install("backend=resthttp,kind=refuse,every=3,seed=2;"
                       "backend=resthttp,op=find,kind=timeout,every=4")
        chaos = [self._query(srv, u) for u in users]
        faults.clear()
        assert chaos == clean, \
            "retries must mask transient read faults: identical " \
            "responses, no degraded flag"
        assert all("degraded" not in r for _, r in chaos)

    def test_blackout_answers_degraded(self, ecomm_stack):
        """Acceptance: under a full event-store blackout >=99% of
        queries answer in degraded mode instead of 500ing."""
        srv, es = ecomm_stack["srv"], ecomm_stack["es"]
        es.stop()  # blackout
        n = 100
        results = [self._query(srv, f"u{i % 12}") for i in range(n)]
        ok = [r for s, r in results if s == 200]
        assert len(ok) >= n * 0.99, \
            f"only {len(ok)}/{n} queries served under blackout"
        assert all(r.get("degraded") is True for r in ok)
        reasons = {x for r in ok for x in r["degradedReasons"]}
        assert reasons & {"circuit_open", "storage_error", "timeout"}
        # the breaker opened, so the tail of the run fast-failed:
        assert resilience.breaker_for(
            ecomm_stack["url"]).state == resilience.OPEN
        assert sum(
            metrics.DEGRADED_QUERIES.value(reason=r)
            for r in ("circuit_open", "storage_error", "timeout")) > 0
        # and this replica now reports NOT ready (balancer drains it)
        checks = srv.health_checks()
        assert checks["deployment"] and checks["device"]
        assert checks["storage"] is False

    @pytest.mark.slow
    def test_long_blackout_then_recovery(self, ecomm_stack,
                                         monkeypatch):
        """Blackout, sustained degraded serving across breaker reset
        cycles (half-open probes keep failing), then a REPLACEMENT
        event server on the same port heals the path: probes close the
        breaker and responses stop being degraded."""
        srv, es = ecomm_stack["srv"], ecomm_stack["es"]
        host, port = es.address
        es.stop()
        br = resilience.breaker_for(ecomm_stack["url"])
        deadline = time.time() + max(
            3.0, 1.5 * br.reset_timeout)
        served = degraded = 0
        while time.time() < deadline:
            s, r = self._query(srv, "u2")
            served += 1
            degraded += bool(s == 200 and r.get("degraded"))
            time.sleep(0.05)
        assert served == degraded, "every blackout query serves degraded"
        # heal: a fresh event server on the SAME address
        reg = StorageRegistry(StorageConfig(
            sources={"S": {"type": "memory"}},
            repositories={"EVENTDATA": "S", "METADATA": "S",
                          "MODELDATA": "S"}))
        es2 = EventServer(EventServerConfig(
            ip=host, port=port, service_key=KEY), reg=reg).start()
        try:
            deadline = time.time() + 3 * br.reset_timeout
            healed = False
            while time.time() < deadline and not healed:
                s, r = self._query(srv, "u2")
                healed = s == 200 and "degraded" not in r
                time.sleep(0.1)
            assert healed, "breaker never closed after the store healed"
            assert srv.health_checks()["storage"] is True
        finally:
            es2.stop()


# ---------------------------------------------------------------------------
# healthz on all four servers
# ---------------------------------------------------------------------------


class TestHealthz:
    def test_event_server_flips_on_breaker(self, mem_storage):
        es = EventServer(EventServerConfig(ip="127.0.0.1",
                                           port=0)).start()
        try:
            status, body, _ = _http_get(es.address, "/healthz")
            assert status == 200
            assert body == {"alive": True, "ready": True,
                            "checks": {"storage": True},
                            "server": "event", "pid": os.getpid()}
            br = resilience.breaker_for("memory")
            for _ in range(br.failure_threshold):
                br.record_failure(TimeoutError())
            status, body, _ = _http_get(es.address, "/healthz")
            assert status == 503
            assert body["alive"] and not body["ready"]
            assert body["checks"]["storage"] is False
        finally:
            es.stop()

    def test_query_server_not_ready_without_deployment(self,
                                                       mem_storage):
        srv = QueryServer(ServerConfig())
        checks = srv.health_checks()
        assert checks["deployment"] is False
        assert checks["device"] is True  # cpu backend answers

    def test_device_probe_hang_is_bounded(self, monkeypatch):
        # a hung backend init BLOCKS inside jax.local_devices() forever;
        # healthz must report not-ready within the probe deadline, not
        # hang the poll — and repeated polls must not stack probe
        # threads behind the wedged one
        import importlib

        import jax

        cs = importlib.import_module(
            "predictionio_tpu.workflow.create_server")

        release = threading.Event()
        calls = []
        real_local_devices = jax.local_devices

        def hung_local_devices():
            calls.append(1)
            release.wait(10.0)
            return real_local_devices()

        monkeypatch.setattr(jax, "local_devices", hung_local_devices)
        monkeypatch.setattr(cs, "_device_ok", None)
        monkeypatch.setattr(cs, "_device_probe_at", 0.0)
        monkeypatch.setattr(cs, "_device_probe_thread", None)
        monkeypatch.setattr(cs, "_DEVICE_PROBE_TIMEOUT", 0.05)
        t0 = time.monotonic()
        assert cs._device_reachable() is False  # bounded, not hung
        assert time.monotonic() - t0 < 5.0
        assert cs._device_reachable() is False  # in-flight: no new probe
        assert len(calls) == 1
        release.set()  # backend recovers; probe thread finishes
        cs._device_probe_thread.join(5.0)
        assert cs._device_reachable() is True  # flips back, no restart

    def test_query_server_http_healthz(self, ecomm_stack):
        srv = ecomm_stack["srv"]
        srv.config.ip, srv.config.port = "127.0.0.1", 0
        srv.start(undeploy_stale=False)
        try:
            status, body, _ = _http_get(srv.address, "/healthz")
            assert status == 200 and body["ready"]
            assert body["checks"] == {"deployment": True, "device": True,
                                      "storage": True}
            br = resilience.breaker_for(ecomm_stack["url"])
            for _ in range(br.failure_threshold):
                br.record_failure(ConnectionRefusedError())
            status, body, _ = _http_get(srv.address, "/healthz")
            assert status == 503 and not body["ready"]
        finally:
            srv.stop()

    def test_admin_and_dashboard_healthz(self, mem_storage):
        from predictionio_tpu.tools.admin_server import (
            AdminServer,
            AdminServerConfig,
        )
        from predictionio_tpu.tools.dashboard import (
            Dashboard,
            DashboardConfig,
        )

        admin = AdminServer(AdminServerConfig(ip="127.0.0.1",
                                              port=0)).start()
        try:
            status, body, _ = _http_get(("127.0.0.1", admin.port),
                                        "/healthz")
            assert status == 200 and body["ready"]
            assert body["server"] == "admin"
        finally:
            admin.stop()
        dash = Dashboard(DashboardConfig(ip="127.0.0.1", port=0)).start()
        try:
            addr = dash._httpd.server_address[:2]
            status, body, _ = _http_get(addr, "/healthz")
            assert status == 200 and body["ready"]
            assert body["server"] == "dashboard"
            br = resilience.breaker_for("memory")
            for _ in range(br.failure_threshold):
                br.record_failure(TimeoutError())
            status, body, _ = _http_get(addr, "/healthz")
            assert status == 503 and not body["ready"]
        finally:
            dash.stop()


# ---------------------------------------------------------------------------
# Micro-batcher queue deadline -> 503 + Retry-After
# ---------------------------------------------------------------------------


class TestMicroBatcherDeadline:
    def test_queued_past_deadline_rejected(self, monkeypatch):
        import numpy as np

        from predictionio_tpu.ops.serving import (
            BatchDispatcher,
            QueryRejectedError,
            _BatchResult,
        )

        monkeypatch.setenv("PIO_QUERY_QUEUE_DEADLINE", "0.2")
        release = threading.Event()
        started = threading.Event()

        class Dummy:
            pass

        def blocking_dispatch(srv, group):
            started.set()
            release.wait(10)
            res = _BatchResult(np.zeros((len(group), 5), dtype=np.int32),
                               np.ones((len(group), 5), dtype=np.float32))
            for row, it in enumerate(group):
                it.future.set_result((res, row))

        server = Dummy()  # kept referenced: the dispatcher weakrefs it
        d = BatchDispatcher(server, window=0.0)
        lane = d.add_lane("pio-test-batch", max_batch=1,
                          dispatch_fn=blocking_dispatch)
        t1 = threading.Thread(target=lambda: lane.submit(0, 5),
                              daemon=True)
        t1.start()
        assert started.wait(5), "first query never dispatched"
        before = metrics.MICROBATCH_REJECTIONS.value(
            batcher="pio-test-batch")
        t0 = time.perf_counter()
        with pytest.raises(QueryRejectedError) as ei:
            # stuck behind the blocked dispatch (max_batch=1 means it
            # can never join the in-flight group)
            lane.submit(1, 5)
        took = time.perf_counter() - t0
        assert 0.15 < took < 5.0, f"rejection took {took}s"
        assert ei.value.retry_after >= 1.0
        assert metrics.MICROBATCH_REJECTIONS.value(
            batcher="pio-test-batch") == before + 1
        assert lane.stats()["rejectedQueries"] == 1
        release.set()
        t1.join(5)
        d.close()

    def test_http_503_with_retry_after(self, monkeypatch, ecomm_stack):
        """The query server maps QueryRejectedError to 503 + the
        standard Retry-After header."""
        from predictionio_tpu.ops.serving import QueryRejectedError
        from predictionio_tpu.workflow import create_server as cs

        srv = ecomm_stack["srv"]

        def overloaded(dep, query):
            raise QueryRejectedError("queue full", retry_after=2.0)

        monkeypatch.setattr(srv, "_predict",
                            staticmethod(overloaded))
        srv.config.ip, srv.config.port = "127.0.0.1", 0
        srv.start(undeploy_stale=False)
        try:
            host, port = srv.address
            conn = http.client.HTTPConnection(host, port, timeout=10)
            conn.request("POST", "/queries.json",
                         body=json.dumps({"user": "u1"}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            assert resp.status == 503
            assert resp.headers["Retry-After"] == "2"
            assert body["retryAfterSec"] == 2.0
            conn.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Feedback loop: bounded retry, never blocks the query — satellite
# ---------------------------------------------------------------------------


class TestFeedbackBounded:
    @pytest.fixture
    def rec_server(self, mem_storage):
        """Recommendation deployment with feedback pointing at an
        in-process event server on the SAME registry."""
        from tests.test_query_server import seed_ratings, train_once

        aid = seed_ratings()
        train_once()
        storage.get_metadata_access_keys().insert(
            AccessKey(key="fbkey", appid=aid))
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0),
                         reg=mem_storage).start()
        qs = QueryServer(ServerConfig(
            ip="127.0.0.1", port=0, feedback=True,
            event_server_ip=es.address[0],
            event_server_port=es.address[1],
            access_key="fbkey")).deploy()
        yield {"es": es, "qs": qs, "app_id": aid}
        es.stop()

    def test_feedback_killed_server_drops_not_delays(self, rec_server):
        qs, es = rec_server["qs"], rec_server["es"]
        # healthy feedback round-trips first
        status, _ = qs.handle_query(b'{"user": "u1"}')
        assert status == 200
        deadline = time.time() + 10
        while time.time() < deadline:
            if list(storage.get_levents().find(
                    app_id=rec_server["app_id"], entity_type="pio_pr")):
                break
            time.sleep(0.05)
        else:
            pytest.fail("healthy feedback event never arrived")
        # kill the event server mid-feedback: the query must neither
        # slow down nor fail, and the drop is counted after 1 retry
        before = metrics.FEEDBACK_DROPPED.value()
        es.stop()
        t0 = time.perf_counter()
        status, result = qs.handle_query(b'{"user": "u1"}')
        took = time.perf_counter() - t0
        assert status == 200 and result["itemScores"]
        assert took < 2.0, \
            f"a dead feedback sink delayed the query by {took}s"
        deadline = time.time() + 5
        while time.time() < deadline and \
                metrics.FEEDBACK_DROPPED.value() <= before:
            time.sleep(0.05)
        assert metrics.FEEDBACK_DROPPED.value() == before + 1


# ---------------------------------------------------------------------------
# Overhead: the fault-free hot path pays almost nothing — perf-marked
# ---------------------------------------------------------------------------


@pytest.mark.perf
@pytest.mark.slow
class TestResilienceOverhead:
    def test_hot_path_overhead_small(self, mem_storage):
        """A CPU guardrail, not a speed: the raw storage-op wrapper
        cost stays single-digit-percent against the kill switch on a
        cheap op."""
        le = storage.get_levents()
        le.init(1)
        ids = le.insert_batch([_event(i) for i in range(50)], 1)

        def lap():
            t0 = time.perf_counter()
            for _ in range(40):
                for eid in ids:
                    le.get(eid, 1)
            return time.perf_counter() - t0

        lap()  # warm
        resilience.set_enabled(True)
        on = min(lap() for _ in range(5))
        resilience.set_enabled(False)
        off = min(lap() for _ in range(5))
        resilience.set_enabled(True)
        assert on <= off * 1.10, \
            f"resilience layer overhead {on / off - 1:.1%} on get()"
