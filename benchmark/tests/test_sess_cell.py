"""The session cell (``seqrec-glm5.sess-extend``): what its shapes
count by hand, its per-layer metrics on hand-made readers and on
nothing, the schedule, the oracle (the package's reference; the tie
rule for both cuts) and the comparison's controls at toy widths."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import cell as cells
from benchmark.harness import sess_check, sess_schedule, shapes_sess
from benchmark.models import sessionrec

CELL = "seqrec-glm5.sess-extend"
NEW_METRICS = ["sess_step_device_ms", "sess_hbm_roofline",
               "dsa_index_roofline", "mla_sparse_roofline",
               "sess_attn_device_share", "sess_moe_device_share",
               "sess_tokens_per_dispatch", "dsa_selected_share",
               "sess_cache_fill_share", "sess_prefill_s"]


def _read(name, readers):
    return cells.load_layer_metric(name)(readers)


# -- operations and bytes --------------------------------------------------------

def test_weights_by_hand_are_the_issues():
    b = sessionrec.block_of(cells.load_cell(CELL).config)
    w = shapes_sess.weights_fixed(b)
    # attention 165.2M + indexer 9.4M a layer, the dense layer's 226.5M,
    # shared expert 37.75M + router 1.57M an expert layer, the slice
    attn = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 \
        + 64 * 256 * 6144
    index = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    want = 6 * (attn + index) + 3 * 6144 * 12288 \
        + 5 * (3 * 6144 * 2048 + 6144 * 256) + 19360 * 6144
    assert w["params"] == want and w["bytes"] == 2 * want
    assert attn == pytest.approx(165.2e6, rel=0.002)
    assert shapes_sess.expert_bytes(b) == 3 * 6144 * 2048 * 2
    # every parameter the chip holds: fixed + input table + 80 experts
    held = want + 19360 * 6144 + 5 * 16 * 3 * 6144 * 2048
    assert held == pytest.approx(4.73e9, rel=0.003)


def test_cache_traffic_by_hand():
    b = sessionrec.block_of(cells.load_cell(CELL).config)
    # one query over 16,384 cached positions: 128 values x 2 bytes a
    # position and layer
    assert shapes_sess.index_key_bytes(16384, b) == 16384 * 6 * 128 * 2
    assert shapes_sess.index_flops(1000, b) == 2 * 1000 * 32 * 128
    s = shapes_sess.sparse_attention(2048 * 6, b)
    assert s["bytes"] == 2048 * 6 * 576 * 2       # 2.4 MB a new token
    assert s["flops"] == 2 * 2048 * 6 * 64 * (576 + 512)
    w = {"dispatches": 2, "tokens": 6, "positions": 40000,
         "selected": 6 * 6 * 2048, "experts_touched": 50}
    got = shapes_sess.dispatch_bytes(w, b)
    want = shapes_sess.weights_fixed(b)["bytes"] + (
        50 * shapes_sess.expert_bytes(b) + 40000 * 6 * 256
        + 6 * 6 * 2048 * 1152 + 6 * 6 * 704 * 2) / 2
    assert got == pytest.approx(want)


# -- the per-layer metrics ---------------------------------------------------------

def _readers():
    b = sessionrec.block_of(cells.load_cell(CELL).config)
    scopes = {"jit_sess_extend": {
        "seconds": 0.2, "count": 20, "kernels": {},
        "scopes": {"sess/index": 0.02, "sess/select": 0.01,
                   "sess/attend": 0.03, "sess/moe/gmm_gate_up": 0.05,
                   "sess/moe": 0.04, "sess/project": 0.04,
                   "sess/head": 0.01}}}
    work = {"kind": "http_sessions", "block": b, "dispatches": 100,
            "tokens": 300.0, "positions": 100 * 3 * 14000.0,
            "selected": 300.0 * 6 * 2048, "eligible": 300.0 * 6 * 14000,
            "experts_touched": 100 * 25.0, "cache_tokens": 350000.0,
            "cache_capacity": 458752.0}
    # the traced slice: a fifth of the window's dispatches, and busier
    # ones (the rooflines read THESE against the slice's device time)
    piece = dict(work, dispatches=20, tokens=80.0,
                 positions=20 * 4 * 14000.0, selected=80.0 * 6 * 2048,
                 eligible=80.0 * 6 * 14000, experts_touched=20 * 30.0)
    return {"trace_scopes": scopes, "work": work, "work_slice": piece,
            "device": {"kind": "TPU v5 lite"}, "spans": {
                "sess_prefill_s": 31.0}}


def test_metrics_on_hand_made_readers():
    r = _readers()
    b = r["work"]["block"]
    assert _read("sess_step_device_ms", r) == pytest.approx(10.0)
    per = shapes_sess.dispatch_bytes(r["work_slice"], b)
    assert _read("sess_hbm_roofline", r) == pytest.approx(
        100 * per / 819e9 / 0.010)
    assert 0 < _read("sess_hbm_roofline", r) < 100
    idx_bytes = 4 * 14000 * 6 * 256
    assert _read("dsa_index_roofline", r) == pytest.approx(
        100 * max(idx_bytes / 819e9,
                  2 * 4 * 6 * 14000 * 32 * 128 / 197e12) / 0.0015)
    att = shapes_sess.sparse_attention(4 * 6 * 2048, b)
    assert _read("mla_sparse_roofline", r) == pytest.approx(
        100 * max(att["bytes"] / 819e9, att["flops"] / 197e12) / 0.0015)
    assert _read("sess_attn_device_share", r) == pytest.approx(30.0)
    assert _read("sess_moe_device_share", r) == pytest.approx(45.0)
    assert _read("sess_tokens_per_dispatch", r) == pytest.approx(3.0)
    assert _read("dsa_selected_share", r) == pytest.approx(
        100 * 2048 / 14000)
    assert _read("sess_cache_fill_share", r) == pytest.approx(
        100 * 350000 / 458752)
    assert _read("sess_prefill_s", r) == 31.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_reads_nothing_where_there_is_nothing(name):
    """Another cell's readers, and a program without the lane, its
    module or its counters (the parent under this benchmark): None,
    never an exception."""
    other = {"work": {"kind": "http_open_loop", "n_items": 5},
             "device": {"kind": "TPU v5 lite"}, "trace": None,
             "trace_scopes": None, "spans": {}}
    assert _read(name, other) is None
    parent = _readers()
    parent["work"] = {"kind": "http_sessions", "dispatches": 0,
                      "block": parent["work"]["block"], "tokens": None,
                      "positions": None, "selected": None, "eligible": None,
                      "experts_touched": None, "cache_tokens": None,
                      "cache_capacity": None}
    parent["work_slice"] = None
    parent["trace_scopes"] = {"jit_users_topk_xla": {
        "seconds": 1.0, "count": 1, "scopes": {}, "kernels": {}}}
    parent["spans"] = {}
    assert _read(name, parent) is None


def test_the_new_entries_list_this_cell_alone():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    serving = ["rec-msd.serve-steady", "twostage-msd.serve-users", CELL]
    for m in bench["end_to_end"]:
        if m["name"] in ("served_qps", "query_p50_ms"):
            assert m["workloads"] == serving
    for name in ("device_idle_share", "batch_mean", "dispatch_p50_us",
                 "queue_wait_p50_us", "handler_host_p50_us",
                 "deploy_ladder_s", "deploy_store_build_s",
                 "deploy_model_load_s"):
        assert by_name[name]["workloads"][-1] == CELL
    for name in ("topk_roofline", "user_lane_device_mean_us"):
        assert CELL not in by_name[name]["workloads"]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and len(bench["workloads"]) == 5
    assert {m["name"] for m in cell.end_to_end} == {
        "served_qps", "query_p50_ms", "setup_s"}
    assert {"hbm_in_use_peak_bytes", "hbm_reserved_bytes", "compile_s",
            "compiles_in_window"} <= {m["name"] for m in cell.per_layer}


# -- the traffic --------------------------------------------------------------------

def test_schedule_is_the_mix():
    mix = cells.load_cell(CELL).traffic
    a = sess_schedule.build_schedule(mix, 24, 19360, 2**31 + 5, 40.0,
                                     rate_qps=100)
    b = sess_schedule.build_schedule(mix, 24, 19360, 2**31 + 5, 40.0,
                                     rate_qps=100)
    assert a["bodies"] == b["bodies"] and a["n_window"] == 4000
    qs = [json.loads(x) for x in a["bodies"]]
    assert all(set(q) == {"user", "items", "num"} for q in qs)
    n = np.asarray([len(q["items"]) for q in qs])
    assert n.min() == 1 and n.max() == 8
    assert n.mean() == pytest.approx(8 / sum(1 / k for k in range(1, 9)),
                                     rel=0.05)          # 2.94
    assert {q["num"] for q in qs} == {10, 20, 50}
    users = np.asarray([int(q["user"][1:]) for q in qs])
    counts = np.bincount(users, minlength=24)
    assert counts.min() > 0 and counts[0] == counts.max()
    assert all(0 <= int(i[1:]) < 19360 for q in qs for i in q["items"])


def test_histories_follow_both_seeds():
    shape = cells.load_cell(CELL).config["shape"]
    n = sessionrec.history_lengths(shape)
    assert len(n) == 24 and n.sum() == 334426
    assert (n.min(), n.max()) == (4962, 30488)
    shape = dict(shape, n_users=3, history_min=5, history_max=9)
    a, b = sessionrec.histories(shape, 7), sessionrec.histories(shape, 8)
    assert [len(a[u]) for u in a] == [len(b[u]) for u in b]
    assert any(a[u].tolist() != b[u].tolist() for u in a)


# -- the oracle and the configuration ------------------------------------------------

def test_oracle_is_the_packages_reference():
    here = os.path.join(cells.ROOT, "benchmark", "harness", "oracle_glm5.py")
    there = os.path.join(cells.ROOT, "predictionio_tpu", "ops",
                         "glm_reference.py")

    def code(path):
        text = open(path).read()
        return text[text.index("from __future__"):].rstrip()

    assert code(here) == code(there)


def test_configuration_keeps_every_published_number():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if '"name": "GLM-5",' in line:
                row = json.loads(line)
    cfg = cells.load_cell(CELL).config
    cut = {"num_hidden_layers": (78, 6), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 16), "vocab_size": (154880, 19360),
           "num_nextn_predict_layers": (1, 0)}
    assert sorted(cfg["reduced"]) == sorted(cut)
    if row is not None:
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cut:
                assert (value, cfg[key]) == cut[key], key
            else:
                assert cfg[key] == value, key
    assert cfg["router_outputs"] == 256
    p = sessionrec.seqrec_params(cfg, seed=1)
    from predictionio_tpu.ops import mla

    g = mla.glm_spec(p)
    assert (g.width, g.n_heads, g.q_rank, g.kv_rank, g.d_nope, g.d_rope,
            g.d_v, g.idx_heads, g.idx_dim, g.idx_topk, g.dense_width,
            g.expert_width, g.n_experts, g.per_token, g.n_shared, g.held,
            g.first, g.n_layers, g.n_dense) == (
        6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048, 256,
        8, 1, 16, 0, 6, 1)
    assert g.route_scale == 2.5 and g.rope_theta == 1e6
    pool = cfg["session"]["pool_tokens"]
    assert pool * 6 * (640 + 128) * 2 == pytest.approx(4.23e9, rel=0.002)
    assert p.session_pool_tokens == pool and p.seeded_weights
    assert p.session_audit == cfg["check"]["audits"]
    assert not [k for k in cfg["env"] if k.startswith("PIO_SESS")]


# -- the comparison that decides ``correct`` ---------------------------------------------

@pytest.fixture(scope="module")
def toy():
    """The rehearsal's block in float32, a lane over one session."""
    import dataclasses

    from predictionio_tpu.ops import mla, seqrec, sessions
    from predictionio_tpu.ops.sessions import SessionTopK

    patch = pytest.MonkeyPatch()
    patch.setattr(sessions, "SESS_BLOCK", 4)
    cfg = cells.load_cell(CELL, rehearse=True).config
    params = dataclasses.replace(sessionrec.seqrec_params(cfg, 5),
                                 compute_dtype="float32")
    theta = seqrec.init_theta_device(200, params)
    st = mla.serving_theta(theta, mla.glm_spec(params))
    events = sessionrec.histories(dict(cfg["shape"], n_users=1,
                                       history_min=50, history_max=50),
                                  5)[0]
    lane = SessionTopK(st["out_emb"][:200], st, params, n_users=1,
                       histories={0: events[:44]}, audit=2,
                       microbatch=False)
    lane.sess_topk(0, events[44:], 10)
    yield lane, theta, sessionrec.block_of(cfg), events
    lane.close()
    patch.undo()


def test_the_lanes_scores_pass_and_the_tie_rule_takes_its_cuts(toy):
    lane, theta, block, events = toy
    got = lane.audits(0)[-1]
    assert (got["slot"], got["queries"], got["bucket"]) == (0, 1, 1)
    rec = {"user": 0, "events": events,
           "answers": [dict(got, tag="probe")]}
    why = []
    out = sess_check.compare(theta, block, [rec],
                             {"q_block": 16, "head_group": 2}, why)
    assert not why and out["worst"]["score_err"] < 1e-4
    assert out["worst"]["layer_err"] < 1e-4
    assert out["worst"]["cache_err"] < 1e-5
    assert out["worst"]["index_regret"] < 1e-6
    assert out["worst"]["gate_err"] < 1e-5
    # a cut the reference cannot take: the lowest-scored position in
    # place of the best one is far outside the margin, and is named
    swapped = dict(got, selected=got["selected"].copy(), tag="swapped")
    from benchmark.harness import oracle_glm5

    forged = np.arange(50)[-16:]          # the newest 16, whatever I says
    swapped["selected"][:] = forged
    why = []
    out = sess_check.compare(theta, block, [dict(rec, answers=[swapped])],
                             {"q_block": 16, "head_group": 2}, why)
    assert out["worst"]["index_regret"] > 0.1
    assert any("index_regret" in w for w in why)
    # a router pick the reference scores last
    ref = oracle_glm5.forward(theta, events, block)
    assert ref["cuts"] == {}
    wrong = dict(got, picks=(got["picks"] + 5) % block["n_experts"],
                 tag="picks")
    why = []
    sess_check.compare(theta, block, [dict(rec, answers=[wrong])],
                       {"q_block": 16, "head_group": 2}, why)
    assert any("router_margin" in w for w in why)


@pytest.mark.parametrize("name", ["sound", *sess_check.CONTROLS])
def test_controls_are_caught(name, monkeypatch):
    """The reference degraded in the lane's place, at the rehearsal's
    widths, through the harness's own comparison (its cuts GIVEN to
    the sound reference, as a lane's are): every control reads over the
    limit of the reading that is there to catch it, and the reference
    undegraded reads under every limit."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = sess_check.control(name, seed=3, rehearse=True, length=40)
    assert set(out["readings"]) == set(sess_check.LIMITS)
    assert out["caught"] == (name != "sound"), out
