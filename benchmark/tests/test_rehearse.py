"""Each cell end to end on the CPU at the toy sizes its files carry,
through the same code as a measured run, each in under a minute. The
training cell's rehearsal includes the full-trajectory comparison with
the copied numpy trainer. The line says ``cpu`` and carries no device
metric."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import cell as cells

WORKLOADS = [w["name"] for w in cells.load_benchmark()["workloads"]]
DEVICE_METRICS = {m["name"] for m in cells.load_benchmark()["per_layer"]
                  if m["source"] == "device_trace"}


def _run(workload, trace):
    t = time.time()
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "4", "--trace", str(trace), "--rehearse"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    last = out.stdout.strip().splitlines()[-1]
    return json.loads(last), out.stderr, time.time() - t


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_rehearses_end_to_end(workload):
    line, err, took = _run(workload, 0)
    assert took < 60
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    cell = cells.load_cell(workload)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    if cell.traffic["kind"] == "train_calls":
        spans = json.loads(err.split("benchmark: spans ")[1].splitlines()[0])
        assert spans["trajectory_rel_err"] < 1e-3
        assert spans["half_step_rel_err"] < 1e-3


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_rehearsal_reports_layers_but_no_device_metric(workload):
    line, _, _ = _run(workload, 1)
    assert line["correct"] is True
    assert line["metrics"] and not set(line["metrics"]) & DEVICE_METRICS
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--trace", "0"], cwd=cells.ROOT,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "{" not in out.stdout
