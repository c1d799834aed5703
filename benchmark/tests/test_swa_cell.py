"""The mixed-session cell's own pieces at toy widths on the CPU: the
configuration against the catalog row, the probes' own session, the
comparison's controls through the same ``compare()`` as a lane's
audits, the shapes' operations and bytes against hand-counted cases,
the per-layer readers on a run without their spans, and the whole cell
rehearsed."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import shapes_swa, swa_check
from benchmark.models import swarec

CELL = "seqrec-smallthinker.sess-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOT = cells.ROOT


def test_configuration_keeps_every_published_number_but_the_depth():
    cell = cells.load_cell(CELL)
    c = cell.config
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_num_primary_experts"],
            c["moe_num_active_primary_experts"], c["moe_ffn_hidden_size"],
            c["vocab_size"], c["sliding_window_size"]) \
        == (2560, 28, 4, 128, 64, 6, 768, 151936, 4096)
    assert "pipeline stage of 8 layers" in c["deployment"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["source_url"] == c["source"]
        differs = [k for k, v in row["config"].items()
                   if c.get(k, "?") != v]
        assert differs == ["num_hidden_layers"]
    mix = cell.traffic
    assert (mix["num"]["values"], mix["num"]["shares"]) \
        == ([10, 20, 50], [0.8, 0.15, 0.05])
    assert mix["user_exponent"] == 0.6 and mix["timeout_ms"] == 5000
    assert mix["events"] == {"min": 1, "max": 8}
    assert mix["knee"]["limit_ms"] == 100
    assert mix["rate_qps"] % 10 == 0
    assert 0.5 * mix["knee"]["found_qps"] <= mix["rate_qps"] \
        <= 0.8 * mix["knee"]["found_qps"]
    shape = c["shape"]
    assert (shape["n_users"], shape["history_min"], shape["history_max"],
            shape["data_seed"]) == (48, 2048, 14336, 39)
    from benchmark.models import sessionrec

    lengths = sessionrec.history_lengths(shape)
    assert (int(lengths.sum()), int(lengths.min()), int(lengths.max()),
            int((lengths < 4096).sum())) == (303019, 2165, 13684, 17)


def test_the_probes_session_is_one_more_user_outside_the_traffic():
    config = cells.load_cell(CELL, rehearse=True).config
    models, _, hist = swarec.build(config, seed=3)
    probe = swarec.probe_user(config)
    assert probe == int(config["shape"]["n_users"]) == max(hist)
    assert len(hist[probe]) == int(config["check"]["probe_session"])
    assert len(models[0].user_map) == probe + 1
    params = swarec.seqrec_params(config, seed=3)
    assert (params.block, params.sliding_window_size, params.max_seq_len) \
        == ("smallthinker", 512, 2048)
    # before the window its probes lie inside the model's window, after
    # it they have crossed it
    n, e = len(hist[probe]), int(config["check"].get("probe_events", 3))
    w = int(config["sliding_window_size"])
    assert n + e < w < n + e * (1 + int(config["check"].get(
        "probes_after", 3)))
    whole = cells.load_cell(CELL).config
    n, w = whole["check"]["probe_session"], whole["sliding_window_size"]
    assert n + 3 < w < n + 3 * 4


@pytest.mark.parametrize("name", ("sound",) + tuple(swa_check.CONTROLS))
def test_control_is_caught_and_the_sound_reference_passes(name):
    """The reference, degraded, in the lane's place, through the lane's
    ``compare()``: each control is caught by the reading named for it;
    the reference undegraded reads zeros."""
    out = swa_check.control(name, seed=5, rehearse=True, length=1064)
    assert out["caught"] == (name != "sound"), out
    if name == "sound":
        assert all(v < 1e-5 for v in out["readings"].values())
    else:
        by = swa_check.CONTROLS[name]
        assert out["readings"][by] > swa_check.LIMITS[by]


def test_shapes_count_picked_experts_and_visible_rows():
    b = swarec.block_of(cells.load_cell(CELL).config)
    fixed = shapes_swa.weights_fixed(b)
    # 8 layers of q, k, v, o in bf16 and a float32 router, and the head
    assert fixed == pytest.approx(
        8 * (20.97e6 * 2 + 2560 * 64 * 4) + 151936 * 2560 * 2, rel=0.001)
    assert fixed == pytest.approx(1.12e9, rel=0.01)
    assert shapes_swa.expert_bytes(b) == 3 * 2560 * 768 * 2
    assert shapes_swa.cache_row_bytes(b) == 2 * 512 * 2
    # 10 dispatches of 1 query with 3 events against 10,000 cached rows:
    # a global layer reads 10,003 rows, a window layer 4,095 + 3
    w = {"dispatches": 10, "queries": 10, "tokens": 30.0,
         "experts_touched": 10 * 8 * 15.0,
         "rows_read_global": 10 * 2 * 10003.0,
         "rows_read_window": 10 * 6 * 4098.0}
    got = shapes_swa.dispatch_bytes(w, b)
    assert got == pytest.approx(
        fixed + 8 * 15 * 11.8e6 + (2 * 10003 + 6 * 4098 + 3 * 8) * 2048,
        rel=0.001)
    # never the 64 held, never the rows held: one table would read
    # 8 x 10,003
    assert got < fixed + 8 * 64 * shapes_swa.expert_bytes(b) / 4
    assert shapes_swa.rows_read(w) < 10 * 8 * 10003
    need = shapes_swa.cache_attention(w, b)
    assert need["bytes"] == (2 * 10003 + 6 * 4098) * 10 * 2048
    assert need["flops"] == 4.0 * (2 * 10003 + 6 * 4098) * 10 * 3 * 28 * 128
    layer = 2 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                 + 3 * 2560 * 768 * 6)
    assert shapes_swa.model_flops(w, b) == pytest.approx(
        30 * 8 * layer + 10 * 2 * 2560 * 151936 + need["flops"])
    # the tables: 1,224 global blocks and 749 window blocks held
    held = {"kind_tokens_global": 1224 * 256.0,
            "kind_tokens_window": 749 * 256.0}
    assert shapes_swa.held_rows(held, b) / shapes_swa.one_table_rows(
        held, b) == pytest.approx((2 * 1224 + 6 * 749) / (8 * 1224))


NEW_METRICS = ("swa_step_device_ms", "swa_mfu", "swa_hbm_roofline",
               "swa_attn_roofline", "swa_moe_device_share",
               "swa_window_rows_share", "swa_blocks_released",
               "swa_experts_touched")


def test_new_metrics_list_the_new_cell_alone_and_read_nothing_from_nothing():
    bench = cells.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by[name]["workloads"] == [CELL]
        read = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read
        # the parent commit: no such module, span or counter
        assert read({"device": {"kind": "TPU v5 lite"}, "swa": None,
                     "swa_slice": None, "swa_module": None}) is None
        assert read({"device": {"kind": "TPU v5 lite"}}) is None
    for name in ("sess_cache_fill_share", "sess_prefill_s",
                 "sess_tokens_per_dispatch", "served_qps", "query_p50_ms",
                 "device_idle_share", "batch_mean"):
        m = by.get(name) or next(e for e in bench["end_to_end"]
                                 if e["name"] == name)
        assert m["workloads"][-1] == CELL
    for name in ("sess_step_device_ms", "dsa_index_roofline",
                 "gqa_cache_attn_roofline", "slate_mfu"):
        assert CELL not in by[name]["workloads"]


def test_readers_on_a_slice_of_counters_and_scopes():
    b = swarec.block_of(cells.load_cell(CELL).config)
    w = {"dispatches": 100, "queries": 130, "tokens": 370.0,
         "experts_touched": 100 * 8 * 20.0,
         "rows_read_global": 130 * 2 * 6000.0,
         "rows_read_window": 130 * 6 * 3500.0, "blocks_released": 7.0,
         "kind_tokens_global": 1250 * 256.0,
         "kind_tokens_window": 760 * 256.0, "block": b}
    module = {"seconds": 0.7, "count": 100,
              "scopes": {"swa/attn/global": 0.05, "swa/attn/window": 0.15,
                         "swa/moe": 0.35, "swa/head": 0.15},
              "kernels": {"swa/attn/global": 0.02, "swa/attn/window": 0.05,
                          "swa/moe": 0.3}}
    r = {"device": {"kind": "TPU v5 lite"}, "swa": w, "swa_slice": w,
         "swa_module": module}

    def read(name):
        return importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(r)

    assert read("swa_step_device_ms") == pytest.approx(7.0)
    assert read("swa_moe_device_share") == pytest.approx(50.0)
    assert read("swa_blocks_released") == 7.0
    assert read("swa_experts_touched") == pytest.approx(20.0)
    assert read("swa_window_rows_share") == pytest.approx(
        100 * (2 * 1250 + 6 * 760) / (8 * 1250))
    for name in ("swa_hbm_roofline", "swa_attn_roofline", "swa_mfu"):
        assert 0 < read(name) < 100


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """``run.py --rehearse``: deploy, probes, a short window, the
    comparison with the reference, one JSON line, ``correct``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--trace", str(trace),
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    if trace:
        assert {"swa_window_rows_share", "swa_blocks_released",
                "swa_experts_touched", "sess_tokens_per_dispatch",
                "sess_cache_fill_share", "batch_mean"} <= names
        assert line["metrics"]["swa_window_rows_share"]["value"] < 100
    else:
        assert names == {"served_qps", "query_p50_ms", "setup_s"}
    assert "Fatal Python error" not in out.stderr
