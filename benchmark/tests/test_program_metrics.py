"""The ten per-layer metrics PR 23 reads out of the program's own
recorders (span stage summaries, flight-record stage stamps, module
names): each reader on hand-made ``readers``, against a program that
has none of it, and in a rehearsal of its cells."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import program_spans
from predictionio_tpu.utils import tracing
from predictionio_tpu.utils.tracing import Span, TraceBuffer

NEW = ["request_span_p50_us", "handler_host_p50_us", "dispatch_gap_p50_us",
       "window_wait_p50_us", "fetch_deliver_p50_us",
       "user_lane_device_mean_us", "train_outside_iters_ms",
       "deploy_model_load_s", "deploy_store_build_s", "deploy_ladder_s"]
QUERY = "query POST /queries.json"


def _read(name, readers):
    return cells.load_layer_metric(name)(readers)


def _root(buf, name, start, duration, children):
    """Flush one hand-made local root: ``children`` are (name, offset,
    duration[, grandchildren]) under the root."""
    tid = tracing.new_trace_id()
    root = Span(tid, tracing.new_span_id(), None, name)
    root.start, root.end = start, start + duration
    buf.root_started(tid)

    def add(parent, items):
        for item in items:
            cname, off, dur = item[:3]
            sp = Span(tid, tracing.new_span_id(), parent.span_id, cname)
            sp.start, sp.end = start + off, start + off + dur
            buf.add_span(sp)
            if len(item) > 3:
                add(sp, item[3])

    add(root, children)
    buf.flush(root, sampled=False)


@pytest.fixture
def buf(monkeypatch):
    b = TraceBuffer(enabled=True, sample_rate=0.0)
    monkeypatch.setattr(tracing, "TRACES", b)
    return b


def _readers(**over):
    r = {"before": {"t": 100.0}, "after": {"t": 200.0}, "flight": [],
         "trace": None}
    r.update(over)
    return r


def test_request_metrics_read_the_windows_query_roots(buf):
    # three requests in the window (20, 30, 40 ms, of which 18, 27, 36
    # inside device.user_topk), one before it, one root of another name
    for start, ms in ((50.0, 900), (110.0, 20), (120.0, 30), (130.0, 40)):
        _root(buf, QUERY, start, ms / 1e3,
              [("query.parse", 0.0, 0.0005),
               ("serve.predict", 0.001, ms * 0.95e-3,
                [("device.user_topk", 0.0015, ms * 0.9e-3,
                  [("device.execute", 0.002, ms * 0.5e-3)])])])
    _root(buf, "query GET /reload", 140.0, 5.0, [])
    r = _readers()
    assert _read("request_span_p50_us", r) == pytest.approx(30_000)
    assert _read("handler_host_p50_us", r) == pytest.approx(3_000)


def test_deploy_metrics_read_the_deploy_root(buf):
    _root(buf, "pio.deploy", 10.0, 20.0,
          [("deploy.load_models", 0.0, 4.0),
           ("store.upload", 4.0, 0.5),
           ("store.bitmap", 4.5, 3.0),
           ("store.upload", 7.5, 1.5),
           ("ladder.plan", 9.0, 0.25),
           ("ladder.compile", 9.25, 10.0,
            [("ladder.lower", 9.5, 3.0), ("ladder.lower", 12.5, 4.0),
             ("device.user_topk", 19.0, 0.125)])])
    r = _readers()
    assert _read("deploy_model_load_s", r) == pytest.approx(4.0)
    assert _read("deploy_store_build_s", r) == pytest.approx(5.0)
    # plan 0.25 + lower 7 + what is left of the parent: 10 - 7 - 0.125
    assert _read("deploy_ladder_s", r) == pytest.approx(10.125)


def test_train_metric_is_the_call_less_its_iterations(buf):
    for start, stage, fetch in ((110.0, 0.05, 0.02), (120.0, 0.07, 0.02),
                                (130.0, 0.09, 0.02), (10.0, 9.0, 9.0)):
        _root(buf, "als.train", start, stage + 3.7 + fetch,
              [("als.stage", 0.0, stage),
               ("als.iterations", stage, 3.7,
                [("als.compile", stage, 1.0)] if start == 110.0 else []),
               ("als.fetch", stage + 3.7, fetch)])
    assert _read("train_outside_iters_ms", _readers()) == pytest.approx(90.0)


def test_dispatch_metrics_read_the_stage_stamps():
    flight = [
        {"lane": "users", "deviceUs": 10_000.0, "gapUs": 5000.0,
         "gapIdleUs": 1000.0, "gapWindowUs": 2000.0, "fetchUs": 300.0,
         "deliverUs": 200.0},
        {"lane": "users", "deviceUs": 10_000.0, "gapUs": 900.0,
         "gapIdleUs": 0.0, "gapWindowUs": 0.0, "fetchUs": 100.0,
         "deliverUs": 100.0},
        {"lane": "items", "deviceUs": 900.0, "gapUs": 2500.0,
         "gapIdleUs": 500.0, "gapWindowUs": 1500.0, "fetchUs": 150.0,
         "deliverUs": 150.0},
        # the first record of a thread, and a direct (unbatched) call
        {"lane": "users", "deviceUs": 9000.0, "gapUs": None,
         "gapIdleUs": 7e6, "gapWindowUs": 0.0, "fetchUs": 1.0,
         "deliverUs": 1.0},
        {"lane": "user", "deviceUs": 9000.0, "fetchUs": 50.0},
    ]
    r = _readers(flight=flight)
    assert _read("dispatch_gap_p50_us", r) == pytest.approx(2000.0)
    assert _read("window_wait_p50_us", r) == pytest.approx(1500.0)
    assert _read("fetch_deliver_p50_us", r) == pytest.approx(300.0)


def test_user_lane_device_time_reads_the_lane_named_modules():
    modules = {"jit_users_topk_fused": {"seconds": 1.8, "count": 200.0},
               "jit_two_topk": {"seconds": 0.2, "count": 50.0},
               "jit_items_topk": {"seconds": 0.5, "count": 90.0},
               "jit__scatter_rows": {"seconds": 9.0, "count": 1.0}}
    r = _readers(trace={"modules": modules})
    assert _read("user_lane_device_mean_us", r) == pytest.approx(8000.0)


def test_a_program_without_the_recorders_reports_nothing(monkeypatch):
    """The parent commit under this benchmark: no summary ring, records
    without stamps, every serving module called ``jit_prog``. Every new
    reader returns None and none raises."""
    class Old:
        enabled = True

    monkeypatch.setattr(tracing, "TRACES", Old())
    old_flight = [{"ts": 150.0, "lane": "users", "deviceUs": 10_000.0,
                   "hostUs": 10_300.0, "queueWaitUs": 14_000.0}]
    r = _readers(flight=old_flight,
                 trace={"modules": {"jit_prog": {"seconds": 1.0,
                                                 "count": 100.0}}})
    assert {n: _read(n, r) for n in NEW} == dict.fromkeys(NEW)
    assert {n: _read(n, _readers()) for n in NEW} == dict.fromkeys(NEW)
    assert program_spans.roots("pio.deploy") == []


def test_entries_are_appended_and_name_layers_the_benchmark_has():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-10:] == NEW
    old_layers = {m["layer"] for m in bench["per_layer"][:-10]}
    for m in bench["per_layer"][-10:]:
        assert m["layer"] in old_layers
        assert m["better"] == "lower" and m["workloads"]
        assert (m["source"] == "device_trace") == \
            (m["name"] == "user_lane_device_mean_us")


def _rehearse(workload):
    # one CPU device, as a cell has one chip: tier-1's conftest asks for
    # eight virtual ones, which would route train() to the sharded
    # trainer, and that one opens no `als.train` root
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "7", "--trace", "1", "--rehearse"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=300,
        env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "workload", [w["name"] for w in cells.load_benchmark()["workloads"]])
def test_rehearsal_reports_every_program_metric_of_the_cell(workload):
    line = _rehearse(workload)
    assert line["correct"] is True
    cell = cells.load_cell(workload)
    mine = [m for m in cell.per_layer if m["name"] in NEW]
    assert mine
    for m in mine:
        if m["source"] == "device_trace":
            assert m["name"] not in line["metrics"]     # no chip, no number
        else:
            assert line["metrics"][m["name"]]["value"] > 0, m["name"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if "request_span_p50_us" in got:
        # the server's span of a request against the client's clock for
        # it (p95: the rehearsal prints no client median with --trace 1)
        assert got["request_span_p50_us"] <= got["query_p95_ms"] * 1e3
        assert got["handler_host_p50_us"] < got["request_span_p50_us"]
        assert got["window_wait_p50_us"] <= got["dispatch_gap_p50_us"]
