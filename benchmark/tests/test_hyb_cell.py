"""The hybrid-session cell's own pieces at toy widths on the CPU: the
configuration against the catalog row, the probes' own session, the
comparison's controls through the same ``compare()`` as a lane's
audits, the shapes' operations and bytes against hand-counted cases,
the per-layer readers on a run without their spans, the benchmark's
copy of the reference, and the whole cell rehearsed. Everything here is
written against the entries PR 48 added, by name: nothing pins the tail
of a list that a later cell appends to."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cell as cells
from benchmark.harness import hyb_check, shapes_hyb
from benchmark.models import hybrec

CELL = "seqrec-falconh1.sess-hybrid"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOT = cells.ROOT
REDUCED = ["num_hidden_layers", "vocab_size"]


def test_configuration_keeps_every_published_number_but_two_keys():
    cell = cells.load_cell(CELL)
    c = cell.config
    assert list(c["reduced"]) == REDUCED
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["mamba_n_heads"],
            c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"],
            c["mamba_d_conv"], c["mamba_chunk_size"], c["mamba_d_ssm"],
            c["intermediate_size"], c["rope_theta"], c["rms_norm_eps"]) \
        == (5120, 20, 4, 128, 32, 128, 256, 2, 4, 128, 4096, 21504,
            100000000000, 1e-05)
    assert (c["num_hidden_layers"], c["vocab_size"],
            c["chips_sharing_the_tables"]) == (6, 65280, 4)
    assert c["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    assert c["vocab_size"] * 8 >= 261120 and c["num_hidden_layers"] >= 4
    assert (c["compute_dtype"], c["state_dtype"]) == ("bfloat16", "float32")
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Falcon-H1-34B-Instruct")
        assert row["source_url"] == c["source"]
        differs = [k for k, v in row["config"].items()
                   if c.get(k, "?") != v]
        assert sorted(differs) == sorted(REDUCED)
    bench = cells.load_benchmark()
    entry = next(e for e in bench["configs"] if e["name"] == "seqrec-falconh1")
    assert entry["reduced"] == REDUCED and entry["source"] == c["source"]
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) \
        == ("seqrec-falconh1", "sess-hybrid", 1)
    mix = cell.traffic
    assert mix["kind"] == "http_sess_hybrid"
    assert (mix["num"]["values"], mix["num"]["shares"]) \
        == ([10, 20, 50], [0.8, 0.15, 0.05])
    assert mix["user_exponent"] == 0.6 and mix["timeout_ms"] == 5000
    assert mix["events"] == {"min": 1, "max": 8}
    assert (mix["generators"], mix["connection_pool"],
            mix["max_failed_share"]) == (2, 96, 0.001)
    assert (mix["knee"]["limit_ms"], mix["knee"]["start_qps"],
            mix["knee"]["factor"], mix["knee"]["step_seconds"]) \
        == (100, 20, 1.25, 10)
    assert mix["rate_qps"] % 10 == 0
    assert 0.7 * mix["knee"]["found_qps"] < mix["rate_qps"] \
        <= 0.8 * mix["knee"]["found_qps"]
    shape = c["shape"]
    assert (shape["n_users"], shape["n_items"], shape["history_min"],
            shape["history_max"], shape["data_seed"]) \
        == (48, 65280, 2048, 14336, 49)
    from benchmark.models import sessionrec

    lengths = sessionrec.history_lengths(shape)
    assert (int(lengths.sum()), int(lengths.min()), int(lengths.max())) \
        == (312828, 2102, 14218)
    # the block kind's pool 85% full or more by the stored sessions,
    # with room for a window's events
    blocks = int(sum(-(-int(n) // 256) for n in lengths)) \
        + -(-c["check"]["probe_session"] // 256)
    assert blocks == 1252
    assert 0.85 <= blocks / (c["session"]["pool_tokens"] // 256) <= 0.95


def test_the_probes_session_is_one_more_user_outside_the_traffic():
    config = cells.load_cell(CELL, rehearse=True).config
    models, _, hist = hybrec.build(config, seed=3)
    probe = hybrec.probe_user(config)
    assert probe == int(config["shape"]["n_users"]) == max(hist)
    assert len(hist[probe]) == int(config["check"]["probe_session"])
    assert len(models[0].user_map) == probe + 1
    params = hybrec.seqrec_params(config, seed=3)
    assert (params.block, params.mamba_n_heads, params.intermediate_size,
            params.max_seq_len) == ("falcon_h1", 8, 96, 4096)
    whole = hybrec.seqrec_params(cells.load_cell(CELL).config, seed=3)
    assert (whole.vocab_rows, whole.n_layers, whole.max_seq_len,
            whole.session_pool_tokens) == (65280, 6, 262144, 368640)
    from predictionio_tpu.ops import falconh1, seqrec

    spec = falconh1.hyb_spec(whole)
    published = falconh1.hyb_spec(seqrec.SeqRecParams(
        **seqrec.FALCON_H1_34B, n_layers=6, compute_dtype="bfloat16"))
    assert spec == published
    b = hybrec.block_of(cells.load_cell(CELL).config)
    assert (b["ssm_mults"], b["mlp_mults"], b["key_mult"]) \
        == (spec.ssm_mults, spec.mlp_mults, spec.key_mult)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    with open(os.path.join(ROOT, "predictionio_tpu", "ops",
                           "falconh1_reference.py")) as f:
        original = f.read()
    with open(os.path.join(ROOT, "benchmark", "harness",
                           "oracle_falconh1.py")) as f:
        assert f.read() == original


@pytest.mark.parametrize("name", ("sound",) + tuple(hyb_check.CONTROLS))
def test_control_is_caught_and_the_sound_reference_passes(name):
    """The reference, degraded, in the lane's place, through the lane's
    ``compare()``: each control is caught by the reading named for it;
    the reference undegraded reads zeros."""
    out = hyb_check.control(name, seed=5, rehearse=True, length=300)
    by = hyb_check.CONTROLS.get(name)
    if name == "sound":
        assert not out["caught"]
        assert all(v < 1e-4 for v in out["readings"].values())
    else:
        assert out["caught"], out
        assert out["readings"][by] > hyb_check.LIMITS[by]
    from benchmark.harness import oracle_falconh1

    assert set(hyb_check.CONTROLS) == set(oracle_falconh1.CONTROLS)


def test_shapes_count_every_weight_slots_and_cached_rows():
    b = hybrec.block_of(cells.load_cell(CELL).config)
    assert (shapes_hyb.d_ssm(b), shapes_hyb.conv_width(b),
            shapes_hyb.in_width(b)) == (4096, 5120, 9248)
    assert shapes_hyb.attn_weights(b) == pytest.approx(31.46e6 * 2,
                                                       rel=0.002)
    assert shapes_hyb.ssm_weights(b) == pytest.approx(68.35e6 * 2,
                                                      rel=0.002)
    assert shapes_hyb.mlp_weights(b) == 3 * 5120 * 21504 * 2
    assert shapes_hyb.layer_weights(b) == pytest.approx(0.860e9, rel=0.002)
    fixed = shapes_hyb.weights_fixed(b)
    assert fixed == pytest.approx(5.16e9 + 65280 * 5120 * 2, rel=0.002)
    assert fixed == pytest.approx(5.83e9, rel=0.002)
    assert shapes_hyb.slot_bytes(b) == 32 * 128 * 256 * 4 + 3 * 5120 * 2 \
        == 4225024
    assert shapes_hyb.cache_row_bytes(b) == 2 * 512 * 2
    # 10 dispatches of 1 query with 3 events against 6,000 cached rows:
    # every layer reads 6,003 rows AND the slot
    slots = 10 * 6 * shapes_hyb.slot_bytes(b)
    w = {"dispatches": 10, "queries": 10, "live_queries": 10.0,
         "tokens": 30.0, "rows_read_attn": 10 * 6 * 6003.0,
         "state_bytes_read": slots, "state_bytes_written": slots}
    got = shapes_hyb.dispatch_bytes(w, b)
    assert got == pytest.approx(
        fixed + 2 * 6 * 4225024 + (6 * 6003 + 3 * 6) * 2048, rel=1e-6)
    need = shapes_hyb.cache_attention(w, b)
    assert need["bytes"] == 6 * 6003 * 10 * 2048
    assert need["flops"] == 4.0 * 6 * 6003 * 10 * 3 * 20 * 128
    step = shapes_hyb.ssd_step(w, b)
    assert step["bytes"] == pytest.approx(
        10 * 6 * shapes_hyb.ssm_weights(b) + 2 * slots)
    token = 2 * (5120 * 9248 + 4096 * 5120) + 2 * 4 * 5120 \
        + 6 * 32 * 128 * 256
    assert step["flops"] == pytest.approx(30 * 6 * token)
    dense = 2 * (2 * 5120 * 2560 + 2 * 5120 * 512) + 6 * 5120 * 21504
    assert shapes_hyb.model_flops(w, b) == pytest.approx(
        step["flops"] + 30 * 6 * dense + 10 * 2 * 5120 * 65280
        + need["flops"])


NEW_METRICS = ("hyb_step_device_ms", "hyb_mfu", "hyb_hbm_roofline",
               "ssd_step_roofline", "ssd_device_share", "hyb_attn_roofline",
               "hyb_mlp_device_share", "hyb_state_bytes_share")


def test_new_metrics_list_the_new_cell_alone_and_read_nothing_from_nothing():
    bench = cells.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by[name]["workloads"] == [CELL]
        read = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read
        # the parent commit: no such module, span or counter
        assert read({"device": {"kind": "TPU v5 lite"}, "hyb": None,
                     "hyb_slice": None, "hyb_module": None}) is None
        assert read({"device": {"kind": "TPU v5 lite"}}) is None
    for name in ("sess_cache_fill_share", "sess_prefill_s",
                 "sess_tokens_per_dispatch", "served_qps", "query_p50_ms",
                 "device_idle_share", "batch_mean", "gen_late_p99_ms",
                 "server_handle_p50_ms", "deploy_ladder_s",
                 "idle_host_share", "dispatch_p50_us"):
        m = by.get(name) or next(e for e in bench["end_to_end"]
                                 if e["name"] == name)
        assert CELL in m["workloads"]
    # every list that names the long-session cell names this one too,
    # but that cell's own metrics
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "seqrec-qwen3next.sess-long" in m.get("workloads", ()) \
                and not m["name"].startswith(("lin_", "gdn_")):
            assert CELL in m["workloads"], m["name"]
    for name in ("sess_step_device_ms", "dsa_index_roofline",
                 "gqa_cache_attn_roofline", "slate_mfu", "swa_mfu",
                 "lin_mfu", "gdn_step_roofline", "lin_attn_roofline",
                 "topk_roofline"):
        assert CELL not in by[name]["workloads"]


def test_readers_on_a_slice_of_counters_and_scopes():
    b = hybrec.block_of(cells.load_cell(CELL).config)
    slot = 6 * shapes_hyb.slot_bytes(b)
    w = {"dispatches": 100, "queries": 230, "live_queries": 225.0,
         "tokens": 660.0, "rows_read_attn": 225 * 6 * 6500.0,
         "state_bytes_read": 225 * slot, "state_bytes_written": 225 * slot,
         "state_slots": 49.0, "slot_bytes": float(slot),
         "kind_tokens_attn": 1260 * 256.0, "block": b}
    module = {"seconds": 1.2, "count": 100,
              "scopes": {"hyb/ssd/proj": 0.04, "hyb/ssd/scan/recurrent": 0.2,
                         "hyb/ssd/conv": 0.02, "hyb/ssd/out": 0.04,
                         "hyb/attn": 0.15, "hyb/mlp": 0.1,
                         "hyb/head": 0.1, "hyb/embed": 0.0, "": 0.55},
              "kernels": {"hyb/attn": 0.09}}
    r = {"device": {"kind": "TPU v5 lite"}, "hyb": w, "hyb_slice": w,
         "hyb_module": module}

    def read(name):
        return importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(r)

    assert read("hyb_step_device_ms") == pytest.approx(12.0)
    assert read("ssd_device_share") == pytest.approx(25.0)
    assert read("hyb_mlp_device_share") == pytest.approx(100 * 0.1 / 1.2)
    assert read("hyb_state_bytes_share") == pytest.approx(
        100 * 49 * slot / (49 * slot + 1260 * 256 * 6 * 2048))
    assert 20 < read("hyb_state_bytes_share") < 35
    for name in ("hyb_hbm_roofline", "ssd_step_roofline",
                 "hyb_attn_roofline", "hyb_mfu"):
        assert 0 < read(name) < 100, name
    # the mixers' weights arrive under no scope: their share of that
    # time counts against the Mamba-2 mixers' step
    share = 6 * shapes_hyb.ssm_weights(b) / shapes_hyb.weights_prefetched(b)
    assert 0.15 < share < 0.17
    with_stream = read("ssd_step_roofline")
    module["scopes"][""] = 0.0
    assert read("ssd_step_roofline") == pytest.approx(
        with_stream * (0.3 + share * 0.55) / 0.3)


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
         "--workload", CELL, "--seed", "3000000048", "--trace", str(trace),
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stderr[-3000:]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    if trace:
        assert {"hyb_state_bytes_share", "sess_tokens_per_dispatch",
                "sess_cache_fill_share", "sess_prefill_s",
                "batch_mean"} <= names
        assert 0 < line["metrics"]["hyb_state_bytes_share"]["value"] < 100
    else:
        assert names == {"served_qps", "query_p50_ms", "setup_s"}
    check = next(json.loads(ln.split("check ", 1)[1])
                 for ln in out.stderr.splitlines() if " check {" in ln)
    assert check["correct"] is True and check["requests_failed"] == 0
    assert set(check["readings"]) == set(hyb_check.LIMITS)
    assert all(got <= limit for got, limit in check["readings"].values())
    assert check["compared"]["states"] >= 6
    assert check["compared"]["steps"] >= 3
    assert "Fatal Python error" not in out.stderr
