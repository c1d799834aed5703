"""The long-session cell's own pieces at toy widths on the CPU: the
configuration against the catalog row, the probes' own session, the
comparison's controls through the same ``compare()`` as a lane's
audits, the shapes' operations and bytes against hand-counted cases,
the per-layer readers on a run without their spans, and the whole cell
rehearsed. Everything here is written against the entries PR 43 added,
by name: nothing pins the tail of a list that a later cell appends
to."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import lin_check, shapes_lin
from benchmark.models import linrec

CELL = "seqrec-qwen3next.sess-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOT = cells.ROOT
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def test_configuration_keeps_every_published_number_but_three_keys():
    cell = cells.load_cell(CELL)
    c = cell.config
    assert list(c["reduced"]) == REDUCED
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["full_attention_interval"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["shared_expert_intermediate_size"], c["router_outputs"],
            c["partial_rotary_factor"], c["rope_theta"]) \
        == (2048, 16, 2, 256, 16, 32, 128, 128, 4, 4, 512, 10, 512, 512,
            0.25, 10000000)
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["chips_sharing_a_layer"]) == (8, 128, 37984, 4)
    assert "FOUR CHIPS SHARE EACH LAYER" in c["deployment"]
    assert (c["compute_dtype"], c["state_dtype"]) == ("bfloat16", "float32")
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == c["source"]
        differs = [k for k, v in row["config"].items()
                   if c.get(k, "?") != v]
        assert sorted(differs) == sorted(REDUCED)
    bench = cells.load_benchmark()
    entry = next(e for e in bench["configs"] if e["name"] == "seqrec-qwen3next")
    assert entry["reduced"] == REDUCED and entry["source"] == c["source"]
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) \
        == ("seqrec-qwen3next", "sess-long", 1)
    mix = cell.traffic
    assert mix["kind"] == "http_sess_long"
    assert (mix["num"]["values"], mix["num"]["shares"]) \
        == ([10, 20, 50], [0.8, 0.15, 0.05])
    assert mix["user_exponent"] == 0.6 and mix["timeout_ms"] == 5000
    assert mix["events"] == {"min": 1, "max": 8}
    assert (mix["generators"], mix["connection_pool"],
            mix["max_failed_share"]) == (2, 96, 0.001)
    assert mix["knee"]["limit_ms"] == 100
    assert mix["rate_qps"] % 10 == 0
    assert 0.5 * mix["knee"]["found_qps"] <= mix["rate_qps"] \
        <= 0.8 * mix["knee"]["found_qps"]
    shape = c["shape"]
    assert (shape["n_users"], shape["history_min"], shape["history_max"],
            shape["data_seed"]) == (32, 8192, 65536, 43)
    from benchmark.models import sessionrec

    lengths = sessionrec.history_lengths(shape)
    assert (int(lengths.sum()), int(lengths.min()), int(lengths.max())) \
        == (906803, 8261, 65247)
    # the attention kind's pool 85% full or more by the stored sessions
    blocks = int(sum(-(-int(n) // 256) for n in lengths)) \
        + -(-c["check"]["probe_session"] // 256)
    assert 0.85 <= blocks / (c["session"]["pool_tokens"] // 256) <= 0.95


def test_the_probes_session_is_one_more_user_outside_the_traffic():
    config = cells.load_cell(CELL, rehearse=True).config
    models, _, hist = linrec.build(config, seed=3)
    probe = linrec.probe_user(config)
    assert probe == int(config["shape"]["n_users"]) == max(hist)
    assert len(hist[probe]) == int(config["check"]["probe_session"])
    assert len(models[0].user_map) == probe + 1
    params = linrec.seqrec_params(config, seed=3)
    assert (params.block, params.n_experts, params.experts_held,
            params.max_seq_len) == ("qwen3_next", 8, 4, 4096)
    whole = linrec.seqrec_params(cells.load_cell(CELL).config, seed=3)
    assert (whole.n_experts, whole.experts_held, whole.expert_share,
            whole.vocab_rows, whole.n_layers, whole.max_seq_len) \
        == (512, 128, 0, 37984, 8, 262144)
    from predictionio_tpu.ops import qwen3next

    spec = qwen3next.lin_spec(whole)
    b = linrec.block_of(cells.load_cell(CELL).config)
    assert (b["rot_dim"], b["first"], b["held"]) \
        == (spec.rot_dim, spec.first, spec.held) == (64, 0, 128)


@pytest.mark.parametrize("name", ("sound",) + tuple(lin_check.CONTROLS))
def test_control_is_caught_and_the_sound_reference_passes(name):
    """The reference, degraded, in the lane's place, through the lane's
    ``compare()``: each control is caught by the reading named for it;
    the reference undegraded reads zeros."""
    out = lin_check.control(name, seed=5, rehearse=True, length=300)
    by = lin_check.CONTROLS.get(name)
    if name == "sound":
        assert not out["caught"]
        assert all(v < 1e-5 for v in out["readings"].values())
    else:
        assert out["caught"], out
        assert out["readings"][by] > lin_check.LIMITS[by]


def test_shapes_count_picked_experts_slots_and_cached_rows():
    b = linrec.block_of(cells.load_cell(CELL).config)
    assert (shapes_lin.n_gdn(b), shapes_lin.n_full(b)) == (6, 2)
    assert shapes_lin.gdn_weights(b) == pytest.approx(33.72e6 * 2, rel=0.002)
    assert shapes_lin.attn_weights(b) == pytest.approx(27.26e6 * 2,
                                                       rel=0.002)
    fixed = shapes_lin.weights_fixed(b)
    # six DeltaNet and two attention mixers, a float32 router and a
    # bf16 shared expert a layer, and the head's slice
    assert fixed == pytest.approx(
        6 * 67.44e6 + 2 * 54.53e6 + 8 * (2048 * 512 * 4 + 3.146e6 * 2)
        + 37984 * 2048 * 2, rel=0.002)
    assert shapes_lin.expert_bytes(b) == 3 * 2048 * 512 * 2
    assert shapes_lin.slot_bytes(b) == 32 * 128 * 128 * 4 + 3 * 8192 * 2
    assert shapes_lin.cache_row_bytes(b) == 2 * 512 * 2
    # 10 dispatches of 1 query with 3 events against 20,000 cached rows:
    # an attention layer reads 20,003 rows, a DeltaNet layer the slot
    slots = 10 * 6 * shapes_lin.slot_bytes(b)
    w = {"dispatches": 10, "queries": 10, "live_queries": 10.0,
         "tokens": 30.0, "experts_touched": 10 * 8 * 7.0,
         "local_picks": 30 * 8 * 2.5, "rows_read_full": 10 * 2 * 20003.0,
         "state_bytes_read": slots, "state_bytes_written": slots}
    got = shapes_lin.dispatch_bytes(w, b)
    assert got == pytest.approx(
        fixed + 8 * 7 * 6.29e6 + 2 * 6 * 2.146e6
        + (2 * 20003 + 3 * 2) * 2048, rel=0.001)
    # never the 128 held, never a row a token in the DeltaNet layers
    assert got < fixed + 8 * 128 * shapes_lin.expert_bytes(b) / 8
    need = shapes_lin.cache_attention(w, b)
    assert need["bytes"] == 2 * 20003 * 10 * 2048
    assert need["flops"] == 4.0 * 2 * 20003 * 10 * 3 * 16 * 256
    step = shapes_lin.gdn_step(w, b)
    assert step["bytes"] == pytest.approx(
        10 * 6 * shapes_lin.gdn_weights(b) + 2 * slots)
    token = 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 2 * 4 * 8192 \
        + 8 * 32 * 128 * 128
    assert step["flops"] == pytest.approx(30 * 6 * token)
    attn = 2 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048)
    common = 2 * (2048 * 512 + 2048 + 3 * 2048 * 512)
    assert shapes_lin.model_flops(w, b) == pytest.approx(
        step["flops"] + 30 * (2 * attn + 8 * common)
        + 600 * 6 * 2048 * 512 + 10 * 2 * 2048 * 37984 + need["flops"])


NEW_METRICS = ("lin_step_device_ms", "lin_mfu", "lin_hbm_roofline",
               "gdn_step_roofline", "gdn_device_share", "lin_attn_roofline",
               "lin_moe_device_share", "lin_state_bytes_share",
               "lin_experts_touched")


def test_new_metrics_list_the_new_cell_alone_and_read_nothing_from_nothing():
    bench = cells.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by[name]["workloads"] == [CELL]
        read = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read
        # the parent commit: no such module, span or counter
        assert read({"device": {"kind": "TPU v5 lite"}, "lin": None,
                     "lin_slice": None, "lin_module": None}) is None
        assert read({"device": {"kind": "TPU v5 lite"}}) is None
    for name in ("sess_cache_fill_share", "sess_prefill_s",
                 "sess_tokens_per_dispatch", "served_qps", "query_p50_ms",
                 "device_idle_share", "batch_mean", "gen_late_p99_ms",
                 "server_handle_p50_ms", "deploy_ladder_s"):
        m = by.get(name) or next(e for e in bench["end_to_end"]
                                 if e["name"] == name)
        assert CELL in m["workloads"]
    # every list that names the session cell before it names this one
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "seqrec-smallthinker.sess-mixed" in m.get("workloads", ()) \
                and not m["name"].startswith("swa_"):
            assert CELL in m["workloads"], m["name"]
    for name in ("sess_step_device_ms", "dsa_index_roofline",
                 "gqa_cache_attn_roofline", "slate_mfu", "swa_mfu",
                 "swa_attn_roofline", "topk_roofline"):
        assert CELL not in by[name]["workloads"]


def test_readers_on_a_slice_of_counters_and_scopes():
    b = linrec.block_of(cells.load_cell(CELL).config)
    slot = 6 * shapes_lin.slot_bytes(b)
    w = {"dispatches": 100, "queries": 130, "live_queries": 125.0,
         "tokens": 370.0, "experts_touched": 100 * 8 * 9.0,
         "local_picks": 370 * 8 * 2.5, "rows_read_full": 125 * 2 * 30000.0,
         "state_bytes_read": 125 * slot, "state_bytes_written": 125 * slot,
         "state_slots": 33.0, "slot_bytes": float(slot),
         "kind_tokens_full": 3600 * 256.0, "block": b}
    module = {"seconds": 0.45, "count": 100,
              "scopes": {"lin/gdn/proj": 0.06, "lin/gdn/rule": 0.08,
                         "lin/gdn/conv": 0.02, "lin/gdn/out": 0.02,
                         "lin/attn": 0.06, "lin/moe": 0.11,
                         "lin/head": 0.05, "": 0.05},
              "kernels": {"lin/attn": 0.04, "lin/moe": 0.1}}
    r = {"device": {"kind": "TPU v5 lite"}, "lin": w, "lin_slice": w,
         "lin_module": module}

    def read(name):
        return importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(r)

    assert read("lin_step_device_ms") == pytest.approx(4.5)
    assert read("gdn_device_share") == pytest.approx(40.0)
    assert read("lin_moe_device_share") == pytest.approx(100 * 0.11 / 0.45)
    assert read("lin_experts_touched") == pytest.approx(9.0)
    assert read("lin_state_bytes_share") == pytest.approx(
        100 * 33 * slot / (33 * slot + 3600 * 256 * 2 * 2048))
    for name in ("lin_hbm_roofline", "gdn_step_roofline",
                 "lin_attn_roofline", "lin_mfu"):
        assert 0 < read(name) < 100, name
    # the mixers' weights arrive under no scope: their share of that
    # time counts against the DeltaNet layers' step
    share = 6 * shapes_lin.gdn_weights(b) / shapes_lin.weights_prefetched(b)
    assert 0.6 < share < 0.75
    with_stream = read("gdn_step_roofline")
    module["scopes"][""] = 0.0
    assert read("gdn_step_roofline") == pytest.approx(
        with_stream * (0.18 + share * 0.05) / 0.18)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """``run.py --rehearse``: deploy, probes, a short window, the
    comparison with the reference (states among it), one JSON line,
    ``correct``, and every compared reading beside its limit on
    stderr."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000043", "--trace", str(trace),
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    if trace:
        assert {"lin_state_bytes_share", "lin_experts_touched",
                "sess_tokens_per_dispatch", "sess_cache_fill_share",
                "sess_prefill_s", "batch_mean"} <= names
        assert 0 < line["metrics"]["lin_state_bytes_share"]["value"] < 100
    else:
        assert names == {"served_qps", "query_p50_ms", "setup_s"}
    check = next(json.loads(ln.split("check ", 1)[1])
                 for ln in out.stderr.splitlines() if " check {" in ln)
    assert check["correct"] is True and check["requests_failed"] == 0
    assert set(check["readings"]) == set(lin_check.LIMITS)
    assert all(got <= limit for got, limit in check["readings"].values())
    assert check["compared"]["states"] >= 6
    assert "Fatal Python error" not in out.stderr
