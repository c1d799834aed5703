"""A cell, a traffic mix and a per-layer metric are added as new files
and entries only: a copy of the benchmark gets dummy configurations
(a training one; a serving one with ANOTHER store precision than any
cell that is there), dummy mixes and a dummy metric dropped into its
directories, and the new cells run with no edit to a file that was
there."""

import json
import os
import shutil
import subprocess
import sys

from benchmark.harness import cell as cells


def test_dropping_files_makes_a_new_cell_runnable(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes()
              for p in (root / "benchmark").rglob("*") if p.is_file()}
    bench = cells.load_benchmark()
    base = json.loads((root / "benchmark/configs/rec-ml20m.json")
                      .read_text())
    base["shape"] = {"n_users": 120, "n_items": 80, "n_events": 2400,
                     "rank": 8, "data_seed": 5}
    base["rehearse"] = {}
    (root / "benchmark/configs/dummy.json").write_text(json.dumps(base))
    (root / "benchmark/traffic/dummy-train.json").write_text(json.dumps(
        {"kind": "train_calls", "why": "dummy", "rehearse_seconds": 1}))
    (root / "benchmark/layer_metrics/dummy_calls.py").write_text(
        "def read(r):\n    return r['work']['calls']\n")
    bench["configs"].append({
        "name": "dummy", "source": base["source"],
        "file": "benchmark/configs/dummy.json", "reduced": [],
        "why": "dummy"})
    bench["workloads"].append({
        "name": "dummy.dummy-train", "config": "dummy",
        "traffic": "dummy-train", "chips": 1, "why": "dummy"})
    bench["per_layer"].append({
        "name": "dummy_calls", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_pairs_per_s", "workloads": ["dummy.dummy-train"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_pairs_per_s":
            m["workloads"].append("dummy.dummy-train")
    # a serving cell whose store is fp32 where every cell that is there
    # states bf16: the oracle's rounding and the roofline's bytes per
    # element follow the configuration's ``store`` block
    serve = json.loads((root / "benchmark/configs/rec-msd.json")
                       .read_text())
    serve["store"] = {"precision": "fp32"}
    serve["env"]["PIO_SERVE_PRECISION"] = "fp32"
    (root / "benchmark/configs/dummy-fp32.json").write_text(
        json.dumps(serve))
    mix = json.loads((root / "benchmark/traffic/serve-steady.json")
                     .read_text())
    mix["rehearse"]["rate_qps"] = 25
    (root / "benchmark/traffic/dummy-serve.json").write_text(
        json.dumps(mix))
    (root / "benchmark/layer_metrics/dummy_store_bytes.py").write_text(
        "def read(r):\n    return r['work']['store_bytes']\n")
    bench["configs"].append({
        "name": "dummy-fp32", "source": serve["source"],
        "file": "benchmark/configs/dummy-fp32.json", "reduced": [],
        "why": "dummy"})
    bench["workloads"].append({
        "name": "dummy-fp32.dummy-serve", "config": "dummy-fp32",
        "traffic": "dummy-serve", "chips": 1, "why": "dummy"})
    bench["per_layer"].append({
        "name": "dummy_store_bytes", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "serving store",
        "moves": "served_qps", "workloads": ["dummy-fp32.dummy-serve"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rec-msd.serve-steady" in m.get("workloads", ()):
            m["workloads"].append("dummy-fp32.dummy-serve")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=cells.ROOT, JAX_PLATFORMS="cpu")
    lines = {}
    for trace in (0, 1):
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload",
             "dummy.dummy-train", "--seed", "3", "--trace", str(trace),
             "--rehearse"], cwd=root, env=env, capture_output=True,
            text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        lines[trace] = json.loads(out.stdout.strip().splitlines()[-1])
    assert lines[0]["correct"] and lines[0]["failed"] == 0
    assert set(lines[0]["metrics"]) == {"train_pairs_per_s", "setup_s"}
    assert lines[1]["metrics"]["dummy_calls"]["value"] >= 1
    assert lines[1]["metrics"]["compiles_in_window"]["value"] == 0
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "dummy-fp32.dummy-serve", "--seed", "3", "--trace", "1",
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    # the store module was found by the configuration's name for it
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["dummy_store_bytes"]["value"] == 4
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_the_batch_mix_kept_for_later_is_a_cell_by_entries_alone(tmp_path):
    """``rec-msd.batchpredict`` left ``workloads`` after the driver's
    check (PERF.md sections 4 and 6); its mix and driver stay, and one
    entry plus its name in the metrics' lists makes it a cell again."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = cells.load_benchmark()
    name = "rec-msd.batchpredict"
    assert name not in {w["name"] for w in bench["workloads"]}
    bench["workloads"].append({
        "name": name, "config": "rec-msd", "traffic": "batchpredict",
        "chips": 1, "why": "kept for later"})
    lists = {"served_qps", "batch_mean", "dispatch_p50_us",
             "topk_roofline", "device_idle_share"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in lists:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=cells.ROOT, JAX_PLATFORMS="cpu")
    lines = {}
    for trace in (0, 1):
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", name,
             "--seed", "4", "--trace", str(trace), "--rehearse"],
            cwd=root, env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        lines[trace] = json.loads(out.stdout.strip().splitlines()[-1])
    assert lines[0]["correct"] and lines[0]["failed"] == 0
    assert set(lines[0]["metrics"]) == {"served_qps", "setup_s"}
    assert lines[0]["metrics"]["served_qps"]["value"] > 0
    assert lines[1]["correct"]
    assert lines[1]["metrics"]["batch_mean"]["value"] > 1
    assert lines[1]["metrics"]["compiles_in_window"]["value"] == 0
