"""One clock for host and device (ISSUE 23): the profiler sink of the
span machinery, stage summaries per local root, the dispatcher's stage
stamps in the flight record, the roots round ``deploy()`` and a direct
``train()``, and the names inside the device programs."""

import glob
import json
import re
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.ops import als, serving
from predictionio_tpu.ops.serving import DeviceTopK
from predictionio_tpu.utils import device_telemetry, metrics, tracing
from predictionio_tpu.utils.tracing import Span, TraceBuffer

from test_device_telemetry import (  # noqa: F401  (fixtures)
    deployed,
    fresh_recorder,
    request,
)

QUERY_ROOT = "query POST /queries.json"
STAGE_ANNOTATIONS = {"batch.idle", "batch.window", "batch.pick",
                     "batch.form", "dispatch.lock", "dispatch.enqueue",
                     "dispatch.wait", "dispatch.fetch", "batch.deliver"}


@pytest.fixture(autouse=True)
def fresh_traces():
    """An empty trace buffer for every test (autouse fixtures come
    first, so a ``deployed`` server's root is the only ``pio.deploy``)."""
    tracing.trace_buffer().reset()
    yield


def _span(name, start, end, parent=None, thread=1, tid="t" * 32):
    sp = Span(tid, tracing.new_span_id(), parent, name)
    sp.start, sp.end, sp.thread = start, end, thread
    return sp


# ---------------------------------------------------------------------------
# stage summaries
# ---------------------------------------------------------------------------

class TestStageSummaries:
    def test_self_time_overlapping_and_cross_thread_children(self):
        root = _span("root", 0.0, 10.0)
        a = _span("a", 1.0, 4.0, root.span_id)
        b = _span("b", 3.0, 6.0, root.span_id, thread=2)  # overlaps a
        c = _span("c", 9.0, 12.0, root.span_id, thread=3)  # outlives root
        a1 = _span("a.child", 1.5, 2.0, a.span_id)
        a2 = _span("a.child", 2.5, 3.5, a.span_id)        # same name adds
        us = tracing.stage_self_times([a1, b, c, a, a2, root])
        # root: 10 - union([1, 6], [9, 10]) = 4
        assert us["root"] == pytest.approx(4e6)
        assert us["a"] == pytest.approx(1.5e6)       # 3 - (0.5 + 1.0)
        assert us["a.child"] == pytest.approx(1.5e6)
        assert us["b"] == pytest.approx(3e6)
        assert us["c"] == pytest.approx(3e6)         # its own duration

    def test_sequential_children_add_up_to_the_root(self):
        root = _span("root", 0.0, 1.0)
        kids = [_span(f"k{i}", 0.1 * i, 0.1 * i + 0.05, root.span_id)
                for i in range(1, 9)]
        us = tracing.stage_self_times(kids + [root])
        assert sum(us.values()) == pytest.approx(1e6)

    def test_ring_is_bounded_and_keeps_what_sampling_dropped(self,
                                                             monkeypatch):
        monkeypatch.setattr(tracing, "STAGE_SUMMARY_RING", 8)
        buf = TraceBuffer(sample_rate=0.0, enabled=True)
        monkeypatch.setattr(tracing, "TRACES", buf)
        for i in range(20):
            with tracing.trace_scope(f"root{i % 2}"):
                with tracing.span("work"):
                    pass
        assert buf.index() == []             # head sampling kept nothing
        got = buf.stage_summaries()
        assert len(got) == 8                 # the ring's bound
        assert [s["root"] for s in got] == ["root0", "root1"] * 4
        assert all(set(s["selfUs"]) == {s["root"], "work"} for s in got)

    def test_window_and_root_filter(self, monkeypatch):
        buf = TraceBuffer(enabled=True)
        monkeypatch.setattr(tracing, "TRACES", buf)
        with tracing.trace_scope("early"):
            pass
        t0 = tracing.span_now()
        with tracing.trace_scope("kept") as kept:
            with tracing.span("stage"):
                time.sleep(0.002)
        with tracing.trace_scope("other"):
            pass
        t1 = tracing.span_now()
        with tracing.trace_scope("kept"):
            pass
        (s,) = buf.stage_summaries(t0, t1, root="kept")
        assert s["traceId"] == kept.trace_id
        assert s["start"] == kept.start
        assert s["durationUs"] == pytest.approx(kept.duration() * 1e6)
        assert sum(s["selfUs"].values()) == pytest.approx(s["durationUs"])
        assert s["selfUs"]["stage"] >= 2000
        assert [x["root"] for x in buf.stage_summaries(t0, t1)] == \
            ["kept", "other"]
        assert len(buf.stage_summaries(root="kept")) == 2
        p50 = buf.stage_p50("kept")
        assert p50["roots"] == 2 and "stage" in p50["selfUsP50"]


# ---------------------------------------------------------------------------
# the profiler sink
# ---------------------------------------------------------------------------

class TestProfilerSink:
    def test_tracing_imports_and_spans_without_jax(self):
        code = (
            "import sys\n"
            "from predictionio_tpu.utils import tracing\n"
            "assert 'jax' not in sys.modules\n"
            "with tracing.trace_scope('root'):\n"
            "    with tracing.span('child'):\n"
            "        assert tracing.annotation('x') is "
            "tracing._NO_ANNOTATION\n"
            "assert 'jax' not in sys.modules\n"
            "assert tracing._annotation_cls is None\n"
            "print(len(tracing.trace_buffer().stage_summaries()))\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "1"

    def test_span_opens_an_annotation_once_jax_is_imported(self,
                                                           monkeypatch):
        import jax

        opened = []

        class Recording(jax.profiler.TraceAnnotation):
            capturing = False

            def __init__(self, name, **kw):
                opened.append((name, kw))
                super().__init__(name, **kw)

            @staticmethod
            def is_enabled():
                return Recording.capturing

        monkeypatch.setattr(tracing, "_annotation_cls", Recording)
        with tracing.trace_scope("idle"):     # no capture running:
            with tracing.span("child"):       # one read, no object
                pass
        assert opened == []
        Recording.capturing = True
        with tracing.trace_scope("root") as root:
            with tracing.span("child"):
                pass
            with tracing.detached_span(
                    "far", parent=tracing.current_trace_context()):
                pass
        with tracing.span("no.trace"):       # log-line span: still lands
            pass
        assert opened == [("root", {"trace_id": root.trace_id}),
                          ("child", {"trace_id": root.trace_id}),
                          ("far", {"trace_id": root.trace_id}),
                          ("no.trace", {})]
        # killed tracing kills the second sink too
        monkeypatch.setattr(tracing.TRACES, "enabled", False)
        assert tracing.annotation("x") is tracing._NO_ANNOTATION

    def test_capture_holds_request_spans_and_dispatcher_stages(
            self, deployed, tmp_path, monkeypatch):
        import jax

        monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path))
        addr = deployed.address
        from predictionio_tpu.ops import serving

        for store in list(serving._live_servers):   # see the stamps test
            store._dispatcher.window = 0.1
        request(addr, "POST", "/queries.json", {"user": "u1", "num": 3})
        time.sleep(1.2)          # the dispatcher reaches its idle wait
        assert request(addr, "POST", "/profile/start")[0] == 200
        for _ in range(3):
            request(addr, "POST", "/queries.json", {"user": "u1", "num": 3})
        status, stopped = request(addr, "POST", "/profile/stop")
        assert status == 200
        (path,) = glob.glob(
            stopped["profileDir"] + "/plugins/profile/*/*.xplane.pb")
        profile = jax.profiler.ProfileData.from_file(path)
        lines = [ln for plane in profile.planes
                 if plane.name.startswith("/host:") for ln in plane.lines]
        by_line = [{e.name: dict(e.stats) for e in ln.events}
                   for ln in lines]
        # the dispatcher thread's line: every stage of a dispatch
        dispatcher = [names for names in by_line if "batch.form" in names]
        assert dispatcher
        assert STAGE_ANNOTATIONS - {"batch.idle"} <= set(dispatcher[0])
        assert any("batch.idle" in names for names in by_line)
        # a handler thread's line: the request's spans with its trace id
        handler = [names for names in by_line if QUERY_ROOT in names]
        assert handler
        spans = handler[0]
        assert {"http.read_body", "query.parse", "query.extract",
                "serve.supplement", "serve.predict", "device.user_topk",
                "serve.serve", "query.render", "http.write"} <= set(spans)
        tid = spans[QUERY_ROOT]["trace_id"]
        assert re.fullmatch(r"[0-9a-f]{32}", tid)
        assert spans["query.parse"]["trace_id"] == tid


# ---------------------------------------------------------------------------
# the dispatcher's stage stamps
# ---------------------------------------------------------------------------

def _store(microbatch=True):
    rng = np.random.default_rng(3)
    X = rng.standard_normal((24, 8)).astype(np.float32)
    Y = rng.standard_normal((16, 8)).astype(np.float32)
    return DeviceTopK(X, Y, seen={0: np.array([1, 2])},
                      microbatch=microbatch)


class TestStageStamps:
    STAMPS = ("gapUs", "gapIdleUs", "gapWindowUs", "pickUs", "formUs",
              "bookUs", "lockWaitUs", "otherUs", "enqueueUs", "fetchUs",
              "deliverUs", "calledTs", "readyTs", "lives", "dispatcher")

    def test_batched_record_holds_every_stamp(self, fresh_recorder):
        srv = _store()
        # a window long enough that a dispatcher thread scheduled late
        # (a loaded test machine) still finds it open
        srv._dispatcher.window = 0.1
        try:
            for uid in range(4):
                srv.user_topk(uid, 5)
            time.sleep(0.05)       # the last record's deliver lands
            recs = [r for r in fresh_recorder.snapshot(10)
                    if r["lane"] == "users"]
        finally:
            srv.close()
        assert len(recs) == 4
        for r in recs:
            assert all(r.get(k) is not None for k in self.STAMPS), r
            assert r["gapIdleUs"] + r["gapWindowUs"] <= r["gapUs"] + 1
            assert r["enqueueUs"] + r["deviceUs"] == \
                pytest.approx(r["hostUs"], abs=0.2)
            # a lone query's own first wait IS the oldest's age
            (life,) = r["lives"]
            assert life["firstWaitUs"] == pytest.approx(r["queueWaitUs"],
                                                        abs=0.2)
            assert life["rounds"] == 1 and life["betweenUs"] == 0.0
            assert r["readyTs"] - r["calledTs"] == \
                pytest.approx(r["hostUs"] / 1e6, abs=1e-6)
            assert r["dispatcher"].startswith("pio-microbatch-dispatcher/")
            # a lone query waits out the batching window
            assert r["gapWindowUs"] >= 1000
        assert len({r["dispatcher"] for r in recs}) == 1

    def test_direct_dispatch_has_no_gap_but_times_its_own_stages(
            self, fresh_recorder):
        srv = _store(microbatch=False)
        srv.users_topk(np.arange(4), 5)
        (r,) = fresh_recorder.snapshot(10)
        assert "gapUs" not in r and "otherUs" not in r
        assert "lives" not in r     # no dispatcher claimed the query
        assert r["lockWaitUs"] >= 0 and r["enqueueUs"] >= 0
        assert r["formUs"] > 0 and r["fetchUs"] > 0
        srv.close()

    def test_killed_lane_reads_no_clock(self, fresh_recorder, monkeypatch):
        fresh_recorder.enabled = False
        calls = []
        monkeypatch.setattr(device_telemetry.time, "monotonic",
                            lambda: calls.append(1) or 0.0)
        with device_telemetry.stage("formUs", "batch.form"):
            pass
        device_telemetry.mark_ready()
        assert not calls
        monkeypatch.undo()
        srv = _store()
        try:
            idx, _ = srv.user_topk(0, 5)
            assert len(idx) == 5
        finally:
            srv.close()
        assert fresh_recorder.snapshot(10) == []

    def test_stamps_tile_the_dispatcher_threads_time(self, fresh_recorder):
        srv = _store()
        try:
            srv.user_topk(0, 5)                 # thread up, programs jitted
            stop = time.monotonic() + 1.0

            def client(uid):
                while time.monotonic() < stop:
                    srv.user_topk(uid, 5)

            threads = [threading.Thread(target=client, args=(u,))
                       for u in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            recs = [r for r in fresh_recorder.snapshot(1 << 20)[::-1]
                    if r.get("gapUs") is not None]
        finally:
            srv.close()
        assert len(recs) > 20 and len({r["dispatcher"] for r in recs}) == 1
        # laid end to end, one thread's records cover the wall clock from
        # the first record's ready stamp to the last's: `ts` is taken
        # when a record is written, just after its block returned
        tiled = sum(r["gapUs"] + r["enqueueUs"] + r["deviceUs"]
                    for r in recs[1:])
        wall = (recs[-1]["ts"] - recs[0]["ts"]) * 1e6
        assert tiled == pytest.approx(wall, rel=0.02)
        # and the named parts, with what is left over, ARE the gap they
        # are parts of: nothing is counted twice, nothing is dropped
        # (the user lane books nothing BEFORE a program call: a record's
        # ``bookUs`` is its own writing and its span, after its ready)
        for prev, r in zip(recs, recs[1:]):
            parts = (prev["fetchUs"] + prev["deliverUs"] + prev["bookUs"]
                     + r["gapIdleUs"] + r["gapWindowUs"] + r["pickUs"]
                     + r["formUs"] + r["lockWaitUs"])
            assert r["otherUs"] >= 0
            assert parts + r["otherUs"] == pytest.approx(r["gapUs"], abs=1)


# ---------------------------------------------------------------------------
# a query's life, stamped by the dispatcher
# ---------------------------------------------------------------------------

class _StubServer:
    """What a ``BatchDispatcher`` needs of its owner: to be alive."""


def _stub_program(group, lane="stub", seconds=0.002):
    """A laddered dispatch in miniature: a 'program' that takes
    ``seconds``, recorded with the stamps ``_dispatch_entry`` passes
    (and like it clock-free with the recorder killed)."""
    if not device_telemetry.enabled():
        return time.sleep(seconds)
    t0e = tracing.span_now()
    t0 = time.monotonic()
    time.sleep(seconds)
    t1 = time.monotonic()
    return device_telemetry.record_dispatch(
        lane=lane, kernel="xla", precision="fp32", aot="hit", k_bucket=1,
        batch=len(group), bucket=8, host_us=(t1 - t0) * 1e6,
        device_us=(t1 - t0) * 0.9e6, lock_wait_us=0.0, called=t0,
        ready=t1, called_ts=t0e)


def _answer(group):
    serving._deliver(group, np.zeros((len(group), 1), np.int32),
                     np.zeros((len(group), 1), np.float32))


def _one_round(srv, group):
    _stub_program(group, lane="plain")
    _answer(group)


class _Rounds:
    """The slate lane in miniature: a query ``(user, rounds)`` takes
    ``rounds`` dispatches; ONE query of a user is open at a time, a
    second one is handed back unopened; ``between`` (if given) runs on
    the dispatcher thread at the end of a query's first round."""

    def __init__(self, between=None):
        self.left = {}          # id(item) -> rounds still to ride
        self.open = {}          # user -> the item that is open
        self.groups = []        # every group, as tuples of payloads
        self.between = between

    def __call__(self, srv, group):
        self.groups.append(tuple(it.payload for it in group))
        riding = []
        for it in group:
            user, rounds = it.payload
            if self.open.setdefault(user, it) is it:
                self.left.setdefault(id(it), rounds)
                riding.append(it)
        _stub_program(riding, lane="rounds")
        done = []
        for it in riding:
            self.left[id(it)] -= 1
            if not self.left[id(it)]:
                done.append(it)
                del self.open[it.payload[0]]
        if self.between is not None:
            between, self.between = self.between, None
            between()
        if done:
            _answer(done)
        back = [it for it in group if it not in done]
        return back or None


@pytest.fixture
def lives_of(monkeypatch):
    """``{payload: (life, delivery - arrival in us)}`` of every query
    delivered while the test runs: :meth:`_Pending.life` is handed the
    monotonic clock the delivery read."""
    seen = {}
    life = serving._Pending.life

    def spy(self, now):
        got = life(self, now)
        seen[self.payload] = (got, (now - self.arrival) * 1e6)
        return got

    monkeypatch.setattr(serving._Pending, "life", spy)
    return seen


def _adds_up(life, total_us):
    assert life["firstWaitUs"] + life["ridingUs"] + life["betweenUs"] \
        == pytest.approx(total_us, abs=0.5)
    assert min(life["firstWaitUs"], life["ridingUs"],
               life["betweenUs"]) >= 0


class TestQueryLives:
    def _dispatcher(self, window=0.0):
        owner = _StubServer()
        return owner, serving.BatchDispatcher(owner, window=window)

    @staticmethod
    def _submit_together(d, lane, payloads):
        """Every payload in the hand-off before the dispatcher thread
        starts: one group, however late this thread is scheduled."""
        start, d._ensure_thread = d._ensure_thread, lambda: None
        try:
            futs = []
            for payload in payloads:
                futs.append(lane.submit_async(payload, 1))
                time.sleep(0.001)       # distinct arrivals, in order
        finally:
            d._ensure_thread = start
        start()
        return futs

    def test_one_round_query(self, fresh_recorder, lives_of):
        owner, d = self._dispatcher(window=0.005)
        lane = d.add_lane("plain", 8, _one_round)
        try:
            res, row = lane.submit_async(("u", 1), 1).result(timeout=10)
        finally:
            d.close()
        life, total = lives_of[("u", 1)]
        _adds_up(life, total)
        assert life["rounds"] == 1 and life["betweenUs"] == 0.0
        assert life["firstWaitUs"] >= 5000          # the window it waited
        assert life["ridingUs"] >= 2000             # the program it rode
        (rec,) = fresh_recorder.snapshot(10)
        assert rec["lives"] == [life] and res.lives[row] == life
        assert rec["calledTs"] < rec["readyTs"] <= res.delivered \
            <= tracing.span_now()

    def test_three_rounds_with_another_lane_owed_a_turn(
            self, fresh_recorder, lives_of):
        owner, d = self._dispatcher()
        futures = {}
        # a query of the OTHER lane arrives while the long one rides its
        # first round: the dispatcher owes that lane the next turn
        script = _Rounds(between=lambda: futures.update(
            plain=plain.submit_async(("v", 1), 1)))
        rounds = d.add_lane("rounds", 8, script)
        plain = d.add_lane("plain", 8, _one_round)
        try:
            rounds.submit_async(("u", 3), 1).result(timeout=10)
            futures["plain"].result(timeout=10)
        finally:
            d.close()
        life, total = lives_of[("u", 3)]
        _adds_up(life, total)
        assert life["rounds"] == 3
        recs = fresh_recorder.snapshot(10)[::-1]
        assert [r["lane"] for r in recs] == \
            ["rounds", "plain", "rounds", "rounds"]
        # between its first two rounds the other lane's whole dispatch
        assert life["betweenUs"] >= recs[1]["hostUs"]
        assert life["ridingUs"] >= sum(r["hostUs"] for r in recs
                                       if r["lane"] == "rounds")
        # the life is on the record of the round that delivered it
        assert [r.get("lives") for r in recs] == \
            [None, [lives_of[("v", 1)][0]], None, [life]]
        _adds_up(*lives_of[("v", 1)])
        # the oldest's age counts its earlier rounds, its first wait not
        assert recs[3]["queueWaitUs"] > life["firstWaitUs"] \
            + sum(r["hostUs"] for r in recs[:3])

    def test_second_query_of_a_user_waits_unopened_behind_the_first(
            self, fresh_recorder, lives_of):
        owner, d = self._dispatcher(window=0.02)
        script = _Rounds()
        lane = d.add_lane("rounds", 8, script)
        try:
            futs = self._submit_together(
                d, lane, [("u", 2), ("u", 1), ("w", 1)])
            results = [f.result(timeout=10) for f in futs]
        finally:
            d.close()
        # one window, one group of three; then the two of user u
        assert script.groups == [(("u", 2), ("u", 1), ("w", 1)),
                                 (("u", 2), ("u", 1)), (("u", 1),)]
        for payload in script.groups[0]:
            _adds_up(*lives_of[payload])
        first, held, other = (lives_of[p][0] for p in script.groups[0])
        assert (first["rounds"], held["rounds"], other["rounds"]) == \
            (2, 3, 1)
        # the dispatcher cannot tell a round a query was held from one
        # it rode: its lane took it into the group either way
        assert held["ridingUs"] > first["ridingUs"] > other["ridingUs"]
        assert held["betweenUs"] > first["betweenUs"] > 0
        recs = fresh_recorder.snapshot(10)[::-1]
        assert [r["lives"] for r in recs] == [[other], [first], [held]]
        assert [res.lives[row] for res, row in results] == \
            [first, held, other]

    def test_lives_are_in_the_groups_order(self, fresh_recorder, lives_of):
        owner, d = self._dispatcher(window=0.05)
        lane = d.add_lane("plain", 8, _one_round)
        try:
            futs = self._submit_together(
                d, lane, [("u", i) for i in range(4)])
            results = [f.result(timeout=10) for f in futs]
        finally:
            d.close()
        (rec,) = fresh_recorder.snapshot(10)
        assert rec["lives"] == [lives_of[("u", i)][0] for i in range(4)]
        assert [row for _, row in results] == [0, 1, 2, 3]
        waits = [life["firstWaitUs"] for life in rec["lives"]]
        assert waits == sorted(waits, reverse=True)     # oldest first
        assert rec["queueWaitUs"] == pytest.approx(waits[0], abs=0.2)

    def test_killed_recorder_stamps_no_life(self, fresh_recorder,
                                            monkeypatch):
        fresh_recorder.enabled = False
        calls = []
        for name in ("claim", "handed_back", "life"):
            monkeypatch.setattr(serving._Pending, name,
                                lambda self, now, _n=name: calls.append(_n))
        monkeypatch.setattr(serving._tracing, "span_now",
                            lambda: calls.append("span_now") or 0.0)
        owner, d = self._dispatcher()
        lane = d.add_lane("rounds", 8, _Rounds())
        try:
            res, row = lane.submit_async(("u", 3), 1).result(timeout=10)
        finally:
            d.close()
        assert not calls
        assert res.lives is None and res.delivered is None
        assert res.telemetry is None and row == 0

    def test_wake_up_is_stamped_on_the_device_span(self, deployed):
        addr = deployed.address
        t0 = tracing.span_now()
        status, _ = request(addr, "POST", "/queries.json",
                            {"user": "u1", "num": 3})
        assert status == 200
        deadline = time.monotonic() + 5.0
        while not (got := tracing.trace_buffer().stage_summaries(
                t0, root=QUERY_ROOT)) and time.monotonic() < deadline:
            time.sleep(0.01)
        (s,) = got
        spans = {sp["name"]: sp for sp in tracing.trace_buffer().get(
            s["traceId"])["spans"]}
        topk, execute = spans["device.user_topk"], spans["device.execute"]
        life = topk["attributes"]["life"]
        assert set(life) == {"firstWaitUs", "rounds", "ridingUs",
                             "betweenUs"}
        assert life in topk["attributes"]["dispatch"]["lives"]
        # the wake-up runs from the delivery, after the dispatch, to
        # the handler thread running again inside its span
        wake = topk["attributes"]["wakeUs"]
        assert 0 < wake <= (topk["end"] - execute["end"]) * 1e6 + 1
        assert "device.wake" not in spans       # no span of its own
        assert s["selfUs"]["device.wake"] == wake
        # device.execute ends before the wake-up starts, both inside
        # device.user_topk: the device.* self times are that span's
        # duration, with the wake-up named or not, and what
        # ``handler_host_p50_us`` subtracts does not move
        device = sum(us for name, us in s["selfUs"].items()
                     if name.startswith("device."))
        assert device == pytest.approx(topk["durationSec"] * 1e6, abs=2.0)
        assert sum(s["selfUs"].values()) == \
            pytest.approx(s["durationUs"], abs=2.0)

    def test_a_wake_attribute_is_a_stage_of_its_own(self):
        root = _span("root", 0.0, 10.0)
        dev = _span("device.user_topk", 1.0, 9.0, root.span_id)
        exe = _span("device.execute", 2.0, 5.0, dev.span_id, thread=2)
        dev.attributes["wakeUs"] = 1.5e6
        us = tracing.stage_self_times([exe, dev, root])
        assert us == {"device.execute": pytest.approx(3e6),
                      "device.wake": pytest.approx(1.5e6),
                      "device.user_topk": pytest.approx(3.5e6),
                      "root": pytest.approx(2e6)}


class TestRecorderLocks:
    def test_counts_a_wait_only_when_another_thread_holds_it(
            self, monkeypatch):
        lock = tracing.CountedLock()
        clock = []
        monkeypatch.setattr(
            tracing.time, "perf_counter",
            lambda _real=time.perf_counter: clock.append(1) or _real())
        for _ in range(100):
            with lock:
                pass
        assert lock.stats() == {"contended": 0, "waitedUs": 0.0}
        assert not clock        # an uncontended acquisition reads none
        held, release = threading.Event(), threading.Event()

        def holder():
            with lock:
                held.set()
                release.wait(5.0)

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(5.0)
        threading.Timer(0.03, release.set).start()
        with lock:
            pass
        t.join(timeout=5.0)
        assert not t.is_alive()
        stats = lock.stats()
        assert stats["contended"] == 1 and len(clock) == 2
        assert 2_000 <= stats["waitedUs"] < 5e6

    def test_both_recorders_report_their_lock(self, fresh_recorder):
        buf = TraceBuffer(enabled=True)
        assert buf.stage_p50("query")["lock"] == buf.lock_stats() == \
            {"contended": 0, "waitedUs": 0.0}
        counts = fresh_recorder.counts()
        assert counts["lockContended"] >= 0 and counts["lockWaitedUs"] >= 0
        held, release = threading.Event(), threading.Event()

        def holder():
            with buf._lock:
                held.set()
                release.wait(5.0)

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(5.0)
        threading.Timer(0.02, release.set).start()
        buf.root_started("t" * 32)          # any path through the lock
        t.join(timeout=5.0)
        assert not t.is_alive()
        lock = buf.stage_p50("query")["lock"]
        assert lock["contended"] == 1 and lock["waitedUs"] >= 1_000


# ---------------------------------------------------------------------------
# roots: a request, deploy(), a direct train()
# ---------------------------------------------------------------------------

class TestRoots:
    def test_request_summary_adds_up_and_feeds_stats(self, deployed,
                                                     capsys):
        addr = deployed.address
        t0 = tracing.span_now()
        for _ in range(5):
            status, _ = request(addr, "POST", "/queries.json",
                                {"user": "u1", "num": 3})
            assert status == 200
        # the client has its answer before the server thread leaves the
        # root's scope: give the last flush a moment
        deadline = time.monotonic() + 5.0
        while True:
            got = tracing.trace_buffer().stage_summaries(t0,
                                                         root=QUERY_ROOT)
            if len(got) == 5 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert len(got) == 5
        for s in got:
            assert {"http.read_body", "query.parse", "query.extract",
                    "serve.supplement", "serve.predict", "serve.serve",
                    "device.user_topk", "device.execute", "query.render",
                    "http.write", QUERY_ROOT} <= set(s["selfUs"])
            assert sum(s["selfUs"].values()) == \
                pytest.approx(s["durationUs"], abs=2.0)
        _, stats = request(addr, "GET", "/stats.json")
        stages = stats["stages"]
        assert stages["roots"] >= 5
        assert stages["durationUsP50"] == pytest.approx(
            statistics.median(s["durationUs"] for s in
                              tracing.trace_buffer().stage_summaries(
                                  root=QUERY_ROOT)), rel=0.01)
        assert stages["selfUsP50"]["device.user_topk"] > 0
        from predictionio_tpu.tools.top_command import render

        text = render(stats, {})
        assert "stages   query p50" in text and "device.user_topk" in text

    def test_deploy_leaves_one_root_with_its_stages(self, deployed):
        (s,) = tracing.trace_buffer().stage_summaries(root="pio.deploy")
        assert {"pio.deploy", "deploy.load_models", "store.bitmap",
                "store.upload", "ladder.plan", "ladder.lower",
                "ladder.compile"} <= set(s["selfUs"])
        # the workers' lowering is serialized and their parent span
        # covers the pool, so even this root adds up to its wall clock
        assert sum(s["selfUs"].values()) == \
            pytest.approx(s["durationUs"], rel=0.01)
        assert s["selfUs"]["ladder.lower"] > 0
        assert tracing.trace_buffer().slow_log() == []   # slow-exempt

    def test_warmup_query_is_a_span(self, mem_storage, monkeypatch):
        from test_device_telemetry import seed_and_train

        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        seed_and_train()
        t0 = tracing.span_now()
        QueryServer(ServerConfig(
            ip="127.0.0.1", port=0,
            warmup_query={"user": "u1", "num": 3})).deploy()
        (s,) = tracing.trace_buffer().stage_summaries(t0,
                                                      root="pio.deploy")
        assert "deploy.warmup_query" in s["selfUs"]
        assert "serve.predict" in s["selfUs"]

    def test_direct_train_leaves_one_root_per_call(self, monkeypatch):
        import jax

        from predictionio_tpu.core.context import workflow_context
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithm,
            IndexedTrainingData,
            PreparatorParams,
            RatingsPreparator,
        )
        from predictionio_tpu.data.bimap import StringIndexBiMap

        # one device: the plain jitted path (the suite's eight virtual
        # devices would route train_als_auto to the sharded trainer)
        one = jax.devices()[:1]
        monkeypatch.setattr(jax, "devices", lambda *a: one)
        metrics.install_jit_compile_listener()
        rng = np.random.default_rng(0)
        n_u, n_i, n = 40, 30, 400
        rows = np.sort(rng.integers(0, n_u, n)).astype(np.int64)
        cols = rng.integers(0, n_i, n).astype(np.int64)
        vals = rng.integers(1, 6, n).astype(np.float32)
        umap = StringIndexBiMap.from_distinct([f"u{i}" for i in range(n_u)])
        imap = StringIndexBiMap.from_distinct([f"i{i}" for i in range(n_i)])
        ctx = workflow_context(mode="train")
        pd = RatingsPreparator(PreparatorParams(bucketed=True)).prepare(
            ctx, IndexedTrainingData(umap, imap, rows, cols, vals))
        algo = ALSAlgorithm(als.ALSParams(rank=6, num_iterations=2,
                                          seed=1))
        t0 = tracing.span_now()
        first = algo.train(ctx, pd)
        second = algo.train(ctx, pd)
        np.testing.assert_array_equal(first.user_factors,
                                      second.user_factors)
        a, b = tracing.trace_buffer().stage_summaries(t0, root="als.train")
        for s in (a, b):
            assert {"als.train", "als.stage", "als.iterations",
                    "als.fetch"} <= set(s["selfUs"])
            assert sum(s["selfUs"].values()) == \
                pytest.approx(s["durationUs"], abs=2.0)
        assert a["selfUs"].get("als.compile", 0) > 0    # a first call
        assert "als.compile" not in b["selfUs"]
        # inside a train run's root the scope is a child span, no root
        with tracing.trace_scope("pio.train", slow_exempt=True):
            algo.train(ctx, pd)
        assert len(tracing.trace_buffer().stage_summaries(
            t0, root="als.train")) == 2
        (run,) = tracing.trace_buffer().stage_summaries(t0,
                                                        root="pio.train")
        assert "als.iterations" in run["selfUs"]


# ---------------------------------------------------------------------------
# names inside the device programs
# ---------------------------------------------------------------------------

def _optimised_hlo(lowered) -> str:
    """The compiled module's text with scopes, source lines and every
    other piece of metadata left out."""
    from jax._src.lib import _jax

    options = _jax.HloPrintOptions()
    options.print_metadata = False
    (module,) = lowered.compile().runtime_executable().hlo_modules()
    return module.to_string(options)


def _bucketed_sides():
    rng = np.random.default_rng(5)
    n_u, n_i, n = 48, 32, 600
    rows = rng.integers(0, n_u, n)
    cols = rng.integers(0, n_i, n)
    vals = rng.integers(1, 6, n).astype(np.float32)
    return als.bucket_ratings_pair(rows, cols, vals, n_u, n_i)


class TestDeviceProgramNames:
    def _lower_trainer(self):
        user_side, item_side = _bucketed_sides()
        params = als.ALSParams(rank=8, num_iterations=2, seed=0)
        args, kw = als._bucketed_call_args(user_side, item_side, params,
                                           "fp32", abstract=True)
        kw["solver"] = "lanes"          # the TPU default, spelled out
        import jax

        # a fresh jit object: the module-level one caches its trace
        return jax.jit(
            als._als_iterations_bucketed_impl,
            static_argnames=tuple(kw)).lower(*args, **kw)

    def test_trainer_hlo_carries_the_scopes_and_nothing_else_changes(
            self, monkeypatch):
        import contextlib

        import jax

        named = self._lower_trainer()
        text = named.as_text(debug_info=True)
        for path in ("user_step/gram", "user_step/gather",
                     "user_step/assemble", "user_step/solve/factor",
                     "user_step/solve/forward", "user_step/solve/backward",
                     "user_step/scatter", "item_step/gather",
                     "item_step/solve/factor"):
            assert path in text, path
        assert "user_step/solve" in named.compile().as_text()
        optimised = _optimised_hlo(named)
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        jax.clear_caches()      # or the scoped trace is handed back
        bare = self._lower_trainer()
        assert "user_step" not in bare.as_text(debug_info=True)
        assert _optimised_hlo(bare) == optimised

    @pytest.mark.parametrize("kernel,module,scopes", [
        ("xla", "jit_users_topk_xla",
         ("gather_q", "seen_rows", "topk", "pack")),
        ("fused", "jit_users_topk_fused",
         ("gather_q", "seen_rows", "topk", "pack")),
    ])
    def test_ladder_program_is_named_for_its_lane(self, monkeypatch,
                                                  kernel, module, scopes):
        import contextlib

        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("PIO_SERVE_KERNEL", kernel)

        def lowered():
            srv = _store(microbatch=False)
            try:
                with srv._store_lock:
                    pre = (srv._X, srv._Y, srv._seen_bits)
                return srv._batch_program(16, 8).lower(
                    *pre, jax.ShapeDtypeStruct((8,), jnp.int32))
            finally:
                srv.close()

        named = lowered()
        text = named.as_text(debug_info=True)
        assert f"module @{module}" in text
        for scope in scopes:
            # under vmap a scope reads `vmap(<scope>)`
            assert f"{scope}/" in text or f"({scope})/" in text, scope
        optimised = _optimised_hlo(named)
        assert optimised.startswith(f"HloModule {module}")
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = lowered()        # a new store: new jit objects, new trace
        assert "gather_q" not in bare.as_text(debug_info=True)
        assert _optimised_hlo(bare) == optimised

    def test_every_lane_has_its_module_name(self):
        import jax
        import jax.numpy as jnp

        srv = _store(microbatch=False)
        try:
            Yn = srv._normalized_items()
            items = srv._items_program(16, 8, 8).lower(
                Yn, jax.ShapeDtypeStruct((8, 8), jnp.int32),
                jax.ShapeDtypeStruct((8, 8), jnp.float32))
            assert "module @jit_items_topk" in items.as_text()
        finally:
            srv.close()

    def test_two_stage_program_names_its_stages(self):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops.twostage import TwoStageTopK

        rng = np.random.default_rng(7)
        X = rng.standard_normal((24, 8)).astype(np.float32)
        Y = rng.standard_normal((40, 8)).astype(np.float32)
        U = rng.standard_normal((24, 4)).astype(np.float32)
        E = rng.standard_normal((40, 4)).astype(np.float32)
        srv = TwoStageTopK(X, Y, U, E, seen={0: np.array([1])},
                           candidates=16, microbatch=False)
        try:
            with srv._store_lock:
                pre = srv._two_args(jax.ShapeDtypeStruct((8,), jnp.int32))
            text = srv._two_program(16, 16).lower(*pre).as_text(
                debug_info=True)
        finally:
            srv.close()
        assert "module @jit_two_topk" in text
        for scope in ("stage1/gather_q", "stage1/topk", "seen_rows",
                      "rerank", "rerank/pack"):
            assert scope in text, scope

    def test_fold_in_and_pallas_kernels_are_named(self):
        import inspect

        from predictionio_tpu.ops import als_pallas

        lowered = als._get_fold_in_jit().lower(
            np.zeros((16, 8), np.float32), np.zeros((8, 8), np.int32),
            np.zeros((8, 8), np.float32), np.zeros((8, 8), np.float32),
            lam=0.1, alpha=1.0, implicit=True, solver="cho",
            precision="fp32", refine=False)
        text = lowered.as_text(debug_info=True)
        assert "module @jit_fold_in_solve" in text
        assert "fold_in/gather" in text and "fold_in/solve" in text
        src = inspect.getsource(als_pallas)
        assert src.count("pl.pallas_call(") == \
            len(re.findall(r'\n        name="[a-z_]+",\n', src)) == 3


# ---------------------------------------------------------------------------
# the ladder's own footprint (ISSUE 23 item 6)
# ---------------------------------------------------------------------------

class TestLadderFootprint:
    def test_counts_temporaries_and_code_not_the_store(self):
        from predictionio_tpu.ops.aot import AOTCache

        class Analysis:
            argument_size_in_bytes = 3_000_000_000    # the whole store
            output_size_in_bytes = 4096
            generated_code_size_in_bytes = 1000

            def __init__(self, temp):
                self.temp_size_in_bytes = temp

        class Program:
            def __init__(self, temp):
                self.temp = temp

            def memory_analysis(self):
                return Analysis(self.temp)

        cache = AOTCache(max_entries=4)
        for i, temp in enumerate((10_000, 70_000, 30_000)):
            cache.put(i, Program(temp))
        # one program runs at a time: scratch for the hungriest one,
        # code for all three, and the arguments are the store's bytes
        assert cache.memory_report() == {
            "entries": 3, "entriesAnalyzed": 3, "tempBytes": 70_000,
            "codeBytes": 3000, "totalBytes": 73_000}

    def test_stats_report_stays_far_under_the_store(self, deployed):
        _, stats = request(deployed.address, "GET", "/stats.json")
        dev = stats["device"]
        (entry,) = dev["stores"]
        mem = entry["aotLadder"]["memory"]
        assert mem["entries"] > 0
        assert dev["aotLadderBytes"] == mem["totalBytes"] == \
            mem["tempBytes"] + mem["codeBytes"]
        assert json.dumps(mem)
