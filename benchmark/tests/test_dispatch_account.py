"""The eleven per-layer metrics PR 36 reads out of the dispatcher's own
account (``harness/dispatch_account.py``): each reader on hand-made
``readers`` against values worked out by hand, the four idle shares
adding up to 100 on a made-up slice, a program without the account, and
a rehearsal of every serving cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import dispatch_account
from predictionio_tpu.utils import device_telemetry, tracing
from predictionio_tpu.utils.tracing import TraceBuffer

from benchmark.tests.test_program_metrics import (  # noqa: F401 (fixture)
    QUERY,
    _read,
    _readers,
    _root,
    buf,
)

NEW = ["program_call_p50_us", "dispatch_other_p50_us",
       "query_first_wait_p50_us", "query_between_rounds_p50_us",
       "query_riding_p50_us", "handler_wake_p50_us", "idle_no_work_share",
       "idle_window_share", "idle_host_share", "idle_call_wait_share",
       "recorder_lock_wait_ms"]
IDLE = NEW[6:10]
SERVING = ["rec-msd.serve-steady", "twostage-msd.serve-users",
           "seqrec-glm5.sess-extend", "seqrec-sdar.slate-gen"]


def _life(first, riding, between=0.0, rounds=1):
    return {"firstWaitUs": first, "rounds": rounds, "ridingUs": riding,
            "betweenUs": between}


def _rec(ts, gap, idle, window, enqueue, device, other=10.0, lives=(),
         thread="pio-microbatch-dispatcher/7", **more):
    rec = {"ts": ts, "lane": "users", "dispatcher": thread, "gapUs": gap,
           "gapIdleUs": idle, "gapWindowUs": window, "otherUs": other,
           "enqueueUs": enqueue, "deviceUs": device}
    if lives:
        rec["lives"] = list(lives)
    rec.update(more)
    return rec


def test_stage_medians_read_the_accounted_records():
    flight = [
        _rec(110.0, 3000.0, 0.0, 2000.0, 800.0, 1000.0, other=30.0),
        _rec(111.0, 900.0, 0.0, 0.0, 1500.0, 1000.0, other=50.0),
        _rec(112.0, 2500.0, 500.0, 1500.0, 900.0, 1000.0, other=40.0),
        # a thread's first record (no gap), a direct call, and a record
        # of a program that tiles no gap: none is read
        _rec(109.0, None, 7e6, 0.0, 9e4, 1000.0, other=None),
        {"ts": 113.0, "lane": "user", "enqueueUs": 7e4, "deviceUs": 9.0},
        {"ts": 114.0, "lane": "users", "gapUs": 100.0, "gapIdleUs": 0.0,
         "gapWindowUs": 0.0, "enqueueUs": 6e4, "deviceUs": 9.0},
    ]
    r = _readers(flight=flight)
    assert _read("program_call_p50_us", r) == pytest.approx(900.0)
    assert _read("dispatch_other_p50_us", r) == pytest.approx(40.0)


def test_life_medians_read_every_delivered_query():
    flight = [
        _rec(110.0, 3000.0, 0.0, 2000.0, 800.0, 1000.0,
             lives=[_life(2100.0, 1900.0), _life(900.0, 1900.0)]),
        _rec(111.0, 900.0, 0.0, 0.0, 800.0, 1000.0),    # a round, nobody done
        _rec(112.0, 900.0, 0.0, 0.0, 800.0, 1000.0,
             lives=[_life(300.0, 61_000.0, 4000.0, rounds=3)]),
        # the direct caller's record carries none
        {"ts": 113.0, "lane": "sess", "enqueueUs": 7e4, "deviceUs": 9.0},
    ]
    r = _readers(flight=flight)
    assert _read("query_first_wait_p50_us", r) == pytest.approx(900.0)
    assert _read("query_riding_p50_us", r) == pytest.approx(1900.0)
    assert _read("query_between_rounds_p50_us", r) == pytest.approx(0.0)
    assert len(dispatch_account.lives(r)) == 3


def test_wake_median_reads_the_windows_query_roots(buf):
    for start, wake_ms in ((50.0, 90.0), (110.0, 0.1), (120.0, 0.3),
                           (130.0, 0.2)):
        _root(buf, QUERY, start, 0.02,
              [("serve.predict", 0.001, 0.018,
                [("device.user_topk", 0.0015, 0.016,
                  [("device.execute", 0.002, 0.008),
                   ("device.wake", 0.012, wake_ms / 1e3)])])])
    r = _readers()
    assert _read("handler_wake_p50_us", r) == pytest.approx(200.0)
    # the wake-up is device.* time: the handler's own work is what it
    # was without the span (20 ms less the 16 of device.user_topk)
    assert _read("handler_host_p50_us", r) == pytest.approx(4000.0)


def _slice(busy_s=1.0):
    """A made-up 3 s slice from epoch 150: 300 dispatches of one thread,
    each 10 ms of gap + call + device, the chip busy a third."""
    trace = {"profile_start_epoch_s": 150.0, "window_s": 3.0,
             "busy_s": busy_s}
    flight = [_rec(150.005 + 0.01 * i, 5000.0, 1500.0, 2500.0, 1000.0,
                   4000.0) for i in range(300)]
    # outside the slice on either side: not counted
    flight += [_rec(149.0, 1e6, 1e6, 0.0, 1000.0, 4000.0),
               _rec(153.5, 1e6, 0.0, 1e6, 1000.0, 4000.0)]
    return _readers(flight=flight, trace=trace)


def test_idle_shares_add_up_to_100_on_a_made_up_slice():
    r = _slice()
    got = {n: _read(n, r) for n in IDLE}
    # 2 s idle: 0.45 s asleep idle, 0.75 s on the window, 0.3 s of other
    # gap, and of the 1.5 s inside calls the chip was busy 1.0
    assert got == pytest.approx({
        "idle_no_work_share": 22.5, "idle_window_share": 37.5,
        "idle_host_share": 15.0, "idle_call_wait_share": 25.0})
    assert sum(got.values()) == pytest.approx(100.0)


def test_idle_shares_need_one_dispatcher_thread_and_a_trace():
    r = _slice()
    r["flight"][7]["dispatcher"] = "pio-microbatch-dispatcher/8"
    assert {n: _read(n, r) for n in IDLE} == dict.fromkeys(IDLE)
    r = _slice()
    r["trace"] = None
    assert {n: _read(n, r) for n in IDLE} == dict.fromkeys(IDLE)
    r = _slice()
    r["trace"]["profile_start_epoch_s"] = None
    assert {n: _read(n, r) for n in IDLE} == dict.fromkeys(IDLE)
    r = _slice(busy_s=3.0)          # never idle: no share of nothing
    assert {n: _read(n, r) for n in IDLE} == dict.fromkeys(IDLE)


def test_lock_wait_reads_both_recorders(monkeypatch):
    b = TraceBuffer(enabled=True)
    monkeypatch.setattr(tracing, "TRACES", b)
    rec = device_telemetry.FlightRecorder(capacity=16, enabled=True)
    monkeypatch.setattr(device_telemetry, "RECORDER", rec)
    assert _read("recorder_lock_wait_ms", _readers()) == 0.0
    b._lock.waited_us = 1500.0
    rec._lock.waited_us = 2500.0
    assert _read("recorder_lock_wait_ms", _readers()) == \
        pytest.approx(4.0)


def test_a_program_without_the_account_reports_nothing(monkeypatch):
    """The parent commit under this benchmark: records that tile no gap
    (no ``otherUs``) and carry no ``lives``, no ``device.wake`` span, no
    counted locks. Every new reader returns None and none raises."""
    class OldBuffer:
        enabled = True

        def stage_summaries(self, t0, t1, root=None):
            return [{"root": QUERY, "start": 150.0, "durationUs": 9000.0,
                     "traceId": "t", "selfUs": {
                         QUERY: 1000.0, "device.user_topk": 6000.0,
                         "device.execute": 2000.0}}]

    class OldRecorder:
        def counts(self):
            return {"recorded": 1, "retained": 1, "evicted": 0,
                    "capacity": 2048}

    monkeypatch.setattr(tracing, "TRACES", OldBuffer())
    monkeypatch.setattr(device_telemetry, "RECORDER", OldRecorder())
    old = [{"ts": 150.5, "lane": "users", "deviceUs": 1000.0,
            "hostUs": 1800.0, "queueWaitUs": 2400.0, "gapUs": 2900.0,
            "gapIdleUs": 0.0, "gapWindowUs": 1900.0, "formUs": 60.0,
            "lockWaitUs": 5.0, "enqueueUs": 800.0, "fetchUs": 90.0,
            "deliverUs": 80.0, "queueWaitMeanUs": 1700.0,
            "dispatcher": "pio-microbatch-dispatcher/7"}]
    r = _readers(flight=old, trace={
        "profile_start_epoch_s": 150.0, "window_s": 3.0, "busy_s": 1.0})
    assert {n: _read(n, r) for n in NEW} == dict.fromkeys(NEW)
    assert {n: _read(n, _readers()) for n in NEW[:10]} == \
        dict.fromkeys(NEW[:10])


def test_entries_are_appended_and_name_layers_the_benchmark_has():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    old_layers = {m["layer"] for m in bench["per_layer"][:at]}
    for m in bench["per_layer"][at:at + len(NEW)]:
        assert m["layer"] in old_layers
        assert m["better"] == "lower" and "bound" not in m
        assert m["workloads"] == (
            SERVING[3:] if m["name"] == "query_between_rounds_p50_us"
            else SERVING)
        assert (m["source"] == "device_trace") == (m["name"] in IDLE)
        assert cells.load_layer_metric(m["name"]) is not None


def _rehearse(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "2147483659", "--trace", "1", "--rehearse"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=300,
        env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", SERVING)
def test_rehearsal_carries_the_new_metrics_that_list_the_cell(workload):
    line = _rehearse(workload)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine = [m for m in cells.load_cell(workload).per_layer
            if m["name"] in NEW]
    assert len(mine) == (11 if workload == SERVING[3] else 10)
    for m in mine:
        if m["source"] == "device_trace":
            assert m["name"] not in got         # no chip, no number
        else:
            assert got[m["name"]] >= 0, m["name"]
    assert got["program_call_p50_us"] > 0
    assert got["query_riding_p50_us"] > got["program_call_p50_us"]
    assert got["handler_wake_p50_us"] > 0
    # a query's life, as its dispatcher stamped it, and its wake-up all
    # lie inside the server's span of the request
    assert got["query_first_wait_p50_us"] + got["handler_wake_p50_us"] \
        < got["request_span_p50_us"]
    if workload == SERVING[3]:
        assert got["query_between_rounds_p50_us"] > 0
